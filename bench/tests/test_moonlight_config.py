"""The Moonlight configuration file and the program cannot drift apart:
the program's config as the serve driver builds it from the file has the
file's latent-attention and expert blocks, and the catalog's published
keys at the file's top level say the same as both."""
import dataclasses
import json
from pathlib import Path

import harness
from repro.configs import get_config
from repro.models.config import MLAConfig

BENCH = Path(__file__).resolve().parents[1]
C = json.loads((BENCH / "configs" / "moonlight_16b_a3b.json").read_text())
DRIVER = harness.load_module(BENCH / "drivers" / "serve.py",
                             "bench_driver_serve_moonlight_test")


def test_driver_builds_the_files_model():
    cfg = DRIVER.model_config(C)
    for k in DRIVER.MODEL_KEYS:
        assert getattr(cfg, k) == C[k], k
    assert cfg.mla == MLAConfig(**C["mla"])
    assert {k: getattr(cfg.moe, k) for k in C["moe"]} == C["moe"]
    assert cfg.n_dense_layers == C["first_k_dense_replace"]
    assert cfg.n_periods == C["n_layers"] - C["first_k_dense_replace"]


def test_published_keys_are_the_programs_config():
    pub = C["published"]
    assert {k: C[k] for k in pub} == pub      # top level = published group
    full = get_config(C["arch"])
    m, e = full.mla, full.moe
    assert (pub["num_hidden_layers"], pub["hidden_size"],
            pub["intermediate_size"], pub["vocab_size"],
            pub["num_attention_heads"], pub["num_key_value_heads"],
            pub["rope_theta"], pub["rms_norm_eps"],
            pub["first_k_dense_replace"], pub["tie_word_embeddings"]) == (
        full.n_layers, full.d_model, full.d_ff, full.vocab_size,
        full.n_heads, full.n_kv_heads, full.rope_theta, full.rms_eps,
        full.n_dense_layers, full.tie_embeddings)
    assert (pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
            pub["qk_rope_head_dim"], pub["v_head_dim"]) == (
        m.q_lora_rank, m.kv_lora_rank, m.nope_head_dim, m.rope_head_dim,
        m.v_head_dim)
    assert (pub["n_routed_experts"], pub["num_experts_per_tok"],
            pub["moe_intermediate_size"], pub["n_shared_experts"],
            pub["scoring_func"], pub["routed_scaling_factor"]) == (
        e.n_experts, e.top_k, e.d_expert, e.n_shared, e.scoring,
        e.routed_scaling_factor)
    # what the program does with every sigmoid router: the noaux_tc
    # correction bias, renormalised gates, no expert groups
    assert (pub["topk_method"], pub["norm_topk_prob"], pub["n_group"],
            pub["topk_group"]) == ("noaux_tc", True, 1, 1)
    assert dataclasses.replace(full, n_layers=C["n_layers"]) == \
        DRIVER.model_config(C)
