"""Configurations beyond dense grouped-query decoders: the operation
count by block kind (latent attention, experts held of a published
count, shared experts, leading dense layers), and the check that a
configuration file states what the program runs, on small
DeepSeek-style and phi-style files built on the registry's
``deepseek-v2-236b`` and ``phi3.5-moe``."""
import pytest

from chipbench import flops, program
from chipbench.harness import Bench

#: DeepSeek-V2-Lite cut to one dense and five expert layers, 8 of its 64
#: experts held, vocabulary 12,800 of 102,400
DEEPSEEK_V2_LITE = {
    "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 16, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "intermediate_size": 10944, "moe_intermediate_size": 1408,
    "n_shared_experts": 2, "num_experts_per_tok": 6, "n_routed_experts": 8,
    "first_k_dense_replace": 1, "num_hidden_layers": 6,
    "vocab_size": 12800,
    "reduced": {"n_routed_experts": {"published": 64},
                "num_hidden_layers": {"published": 27},
                "vocab_size": {"published": 102400}},
}

#: phi3.5-moe cut to 4 layers, 8 of its 16 experts held, vocabulary 4,008
PHI35_MOE = {
    "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 6400, "num_local_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 4, "vocab_size": 4008,
    "reduced": {"num_local_experts": {"published": 16},
                "num_hidden_layers": {"published": 32},
                "vocab_size": {"published": 32064}},
}


def test_the_qwen2_count_is_unchanged():
    cfg = Bench().config("qwen2-7b-2l")
    assert flops.per_token(cfg, 1024, weight_grads=True) == 3_249_383_424
    assert flops.per_token(cfg, 1024, weight_grads=False) == 2_180_950_016


def test_a_latent_attention_expert_model_is_counted_by_block_kind():
    cfg = DEEPSEEK_V2_LITE
    d, h, ctx = 2048, 16, 512.5
    attn = d * h * 192 + d * (512 + 64) + 512 * h * (128 + 128) + h * 128 * d
    dense = 3 * d * 10944
    # router at 64 outputs, 2 shared experts, 6 of 64 routed experts a
    # token of which 8 lie here: 6 · 8/64 expected on this chip
    moe = d * 64 + 2 * 3 * d * 1408 + 6 * 8 / 64 * 3 * d * 1408
    matrices = 6 * attn + dense + 5 * moe + d * 12800
    attention = 6 * (2 * ctx * 192 + 2 * ctx * 128) * h
    assert flops.per_token(cfg, 1024, True) == 6 * matrices + 3 * attention
    assert flops.per_token(cfg, 1024, True) == 1_868_261_376


def test_an_expert_model_that_names_its_expert_width_intermediate_size():
    cfg = PHI35_MOE
    assert flops.experts(cfg) == (8, 16)
    assert flops.per_token(cfg, 1024, True) == 3_094_904_832
    whole = dict(cfg, num_local_experts=16, reduced={})
    # all 16 held: both of a token's 2 experts here, not 2 · 8/16
    assert flops.ffn_params(whole) - flops.ffn_params(cfg) == \
        4 * (2 - 1) * 3 * 4096 * 6400


def _deepseek_file() -> dict:
    return {
        "name": "deepseek-tiny", "arch_id": "deepseek-v2-236b",
        "dtype": "float32", "hidden_size": 64, "num_hidden_layers": 3,
        "vocab_size": 128, "rms_norm_eps": 1e-6, "num_attention_heads": 4,
        "num_key_value_heads": 4, "rope_theta": 10000.0,
        "attention_bias": False, "intermediate_size": 128,
        "kv_lora_rank": 16, "q_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
        "n_shared_experts": 1, "num_experts_per_tok": 2,
        "n_routed_experts": 4, "first_k_dense_replace": 1,
        "routed_scaling_factor": 1.0, "norm_topk_prob": False,
        "reduced": {"n_routed_experts": {"published": 8}},
        "program": {
            "n_layers": 3, "d_model": 64, "vocab": 128, "mla.d_model": 64,
            "mla.n_heads": 4, "mla.q_lora": 32, "mla.kv_lora": 16,
            "mla.qk_nope": 16, "mla.qk_rope": 8, "mla.v_dim": 16,
            "moe.d_model": 64, "moe.d_ff": 32, "moe.n_experts": 8,
            "moe.top_k": 2, "moe.n_shared": 1, "moe.routed_scale": 1.0,
            "dense_prefix_mlp.d_model": 64, "dense_prefix_mlp.d_ff": 128},
    }


def _phi_file() -> dict:
    return {
        "name": "phi-tiny", "arch_id": "phi3.5-moe", "dtype": "float32",
        "hidden_size": 64, "num_hidden_layers": 2, "vocab_size": 128,
        "rms_norm_eps": 1e-6, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000.0,
        "attention_bias": False, "intermediate_size": 96,
        "num_local_experts": 2, "num_experts_per_tok": 2,
        "norm_topk_prob": True,
        "reduced": {"num_local_experts": {"published": 4}},
        "program": {
            "n_layers": 2, "d_model": 64, "vocab": 128, "attn.d_model": 64,
            "attn.n_heads": 4, "attn.n_kv": 2, "attn.head_dim": 16,
            "attn.head_multiple": 1, "moe.d_model": 64, "moe.d_ff": 96,
            "moe.n_experts": 4, "moe.top_k": 2},
    }


@pytest.mark.parametrize("make", [_deepseek_file, _phi_file])
def test_a_file_that_states_what_the_program_runs_is_accepted(make):
    cfg = make()
    _, lm = program.lm_config(cfg)
    assert lm.moe.n_experts == cfg["reduced"][
        next(k for k in flops.EXPERT_KEYS if k in cfg)]["published"]


def _checked(make) -> list:
    cfg = make()
    keys = [k for k in program.CHECKED_FIELDS if k in cfg]
    return [(make, k) for k in keys + [k for k in flops.EXPERT_KEYS
                                       if k in cfg]]


@pytest.mark.parametrize("make,key", _checked(_deepseek_file)
                         + _checked(_phi_file))
def test_each_key_that_differs_from_the_program_is_refused(make, key):
    cfg = make()
    value = cfg[key]
    if key in flops.EXPERT_KEYS:
        cfg["reduced"][key]["published"] += 1
    elif isinstance(value, bool):
        cfg[key] = not value
    elif value is None:
        cfg[key] = 7
    else:
        cfg[key] = value * 2
    with pytest.raises(ValueError, match=key):
        program.lm_config(cfg)
