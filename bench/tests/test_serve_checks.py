"""The serving cells' comparison, driven end to end on this host with a
small model of the same architecture (the harness's look for a chip
skipped): a sound run is correct, the float8 control in its place makes
``correct`` false, and so does a token altered where the step produces
it."""
import time

import pytest

import harness

# Four layers and 96 served tokens a request: at this size the float8
# control reads 0.41-0.59 over six seeds against the limit of 0.33 (a
# smaller model's control read as low as 0.22).
CFG = {"n_layers": 4, "d_model": 128, "n_heads": 8, "n_kv_heads": 8,
       "head_dim": 16, "d_ff": 352, "vocab_size": 2048,
       "serving": {"n_replicas": 1, "n_slots": 4, "max_len": 160,
                   "page_size": 16, "decode_kernel": "flash"}}
MIX = {"prompt": {"dist": "fixed", "value": 48},
       "output": {"dist": "fixed", "value": 96}, "lead_in_s": 1.0}


def drive(cell, seed, tmp_path):
    spec = harness.load_spec()
    run, driver = harness.prepare(
        spec, cell, seed=seed, seconds=2.0, trace=False, t_start=time.time(),
        config_override=CFG, mix_override=MIX)
    run.out_dir = tmp_path
    run.compiles = harness.CompileCounter()
    driver.run(run)
    return run, driver


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    return drive("stablelm.batch", 3, tmp_path_factory.mktemp("sound"))


def test_sound_batch_run_is_correct(sound):
    run, _ = sound
    assert run.correct, run.checks
    assert run.failed == 0 and run.attempted > 0
    assert run.e2e["output_tokens_per_s"] > 0


def test_control_reads_far_above_the_program(sound):
    run, driver = sound
    ctl = driver.control_checks(run)
    assert ctl["served_gap"] > 3 * run.checks["served_gap"][0]
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
    run, _ = drive("stablelm.batch", 4, tmp_path)
    assert not run.correct
    assert run.checks["served_gap"][0] > run.checks["served_gap"][1]
    assert run.e2e["output_tokens_per_s"] > 0
