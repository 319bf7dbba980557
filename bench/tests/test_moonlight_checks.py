"""The Moonlight cell's comparison, driven end to end on this host with a
small model of the same architecture (one dense and five expert layers,
latent attention without a query bottleneck, sigmoid routing with a bias,
shared experts): a sound run is correct, the float8 control in its place
makes ``correct`` false, and so does a token altered where the step
produces it.

The program runs in float32 here, where it matches the reference exactly,
which leaves the machinery under test. Depth sets the control's reading:
with 96 served tokens a request, its worst request's mean gap read
0.67-0.82 over five seeds at six layers, 0.54-0.65 at five and 0.47 at
four, and three layers read 0.32, under the limit."""
import dataclasses
import time

import pytest

import harness
import repro.configs
from repro.models.config import MLAConfig

CFG = {"n_layers": 6, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
       "head_dim": 32, "d_ff": 256, "vocab_size": 2048, "dtype": "float32",
       "mla": {"q_lora_rank": None, "kv_lora_rank": 32, "rope_head_dim": 16,
               "nope_head_dim": 32, "v_head_dim": 32},
       "moe": {"n_experts": 8, "top_k": 2, "d_expert": 64, "n_shared": 2,
               "scoring": "sigmoid", "routed_scaling_factor": 2.446},
       "serving": {"n_replicas": 1, "n_slots": 4, "max_len": 160,
                   "page_size": 16, "decode_kernel": "flash"}}
MIX = {"prompt": {"dist": "fixed", "value": 48},
       "output": {"dist": "fixed", "value": 96}, "lead_in_s": 1.0}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The serve driver takes the latent and expert dims from the
    architecture; give it this file's."""
    real = repro.configs.get_config

    def get_config(name):
        cfg = real(name)
        return cfg.with_(mla=MLAConfig(**CFG["mla"]),
                         moe=dataclasses.replace(cfg.moe, **CFG["moe"]))
    monkeypatch.setattr(repro.configs, "get_config", get_config)


def drive(seed, tmp_path):
    spec = harness.load_spec()
    run, driver = harness.prepare(
        spec, "moonlight.decode_heavy", seed=seed, seconds=2.0, trace=False,
        t_start=time.time(), config_override=CFG, mix_override=MIX)
    run.out_dir = tmp_path
    run.compiles = harness.CompileCounter()
    driver.run(run)
    return run, driver


def test_sound_run_is_correct_and_the_control_is_not(tmp_path):
    run, driver = drive(3, tmp_path)
    assert run.correct, run.checks
    assert run.failed == 0 and run.attempted > 0
    assert run.e2e["output_tokens_per_s"] > 0
    ctl = driver.control_checks(run)
    assert ctl["served_gap"] > run.checks["served_gap"][1]
    assert not run.correct_with(ctl), ctl


def test_token_altered_where_the_step_produces_it(tmp_path, monkeypatch):
    from repro.serve import engine

    real = engine.make_serve_step

    def altered(cfg, **kw):
        step = real(cfg, **kw)

        def wrong(*args):
            logits, next_id, caches = step(*args)
            return logits, (next_id + 1) % cfg.vocab_size, caches
        return wrong

    monkeypatch.setattr(engine, "make_serve_step", altered)
    run, _ = drive(4, tmp_path)
    assert not run.correct
    assert run.checks["served_gap"][0] > run.checks["served_gap"][1]
