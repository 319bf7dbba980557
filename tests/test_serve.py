"""Serving-tier tests: flash-decode kernel parity (dense + paged, Pallas
interpret vs XLA lowering vs a naive oracle), page-allocator invariants,
paged/flash engine parity against whole-sequence greedy decoding,
eviction-mid-generation resume, and the replicated router's SLO admission
and exact request accounting."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels.flash_decode import (flash_decode, flash_decode_paged,
                                        flash_decode_paged_xla,
                                        flash_decode_xla)
from repro.models import init_params, model_spec
from repro.models.transformer import forward
from repro.obs import phase
from repro.obs.metrics import MetricsRegistry
from repro.serve import (PageAllocator, ServeEngine, ServeReplicaSet,
                         register_serve_metrics, ttft_slo)
from repro.serve import engine as serve_engine
from repro.serve import replica as serve_replica


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("stablelm_1_6b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0),
                         jnp.dtype(cfg.dtype))
    return cfg, params


def _greedy_reference(cfg, params, prompt, max_new):
    toks = list(prompt)
    for _ in range(max_new):
        logits, _, _ = forward(params, cfg,
                               {"tokens": jnp.asarray([toks], jnp.int32)})
        logits = logits[0, -1, :cfg.vocab_size]
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# kernel parity: dense flash-decode
# ---------------------------------------------------------------------------

def _oracle(q, k, v, qpos, kpos, window=None):
    """Naive per-(slot, head) softmax attention over valid key positions."""
    q, k, v = np.asarray(q), np.asarray(k), np.asarray(v)
    qpos, kpos = np.asarray(qpos), np.asarray(kpos)
    b, _, h, dk = q.shape
    g = h // k.shape[2]
    out = np.zeros((b, 1, h, v.shape[3]), np.float32)
    for bi in range(b):
        mask = (kpos[bi] >= 0) & (kpos[bi] <= qpos[bi])
        if window is not None:
            mask &= kpos[bi] > qpos[bi] - window
        if not mask.any():
            continue
        for hi in range(h):
            s = (k[bi, mask, hi // g] @ q[bi, 0, hi]) * dk ** -0.5
            w = np.exp(s - s.max())
            w /= w.sum()
            out[bi, 0, hi] = w @ v[bi, mask, hi // g]
    return out


def _rand_qkv(rng, b, s, h, kh, dk, dv=None):
    dv = dk if dv is None else dv
    return (jnp.asarray(rng.standard_normal((b, 1, h, dk)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, s, kh, dk)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, s, kh, dv)), jnp.float32))


def _dense_kpos(qpos, s):
    """Contiguous-cache positions: slot index = position, -1 past the end."""
    pos = np.tile(np.arange(s, dtype=np.int32), (len(qpos), 1))
    return jnp.asarray(np.where(pos <= np.asarray(qpos)[:, None], pos, -1))


@pytest.mark.parametrize("kh", [4, 2, 1])  # GQA group sizes 1, 2, 4
def test_flash_decode_parity_causal_ragged(kh):
    rng = np.random.default_rng(0)
    q, k, v = _rand_qkv(rng, b=3, s=96, h=4, kh=kh, dk=16)
    qpos = jnp.asarray([5, 40, 95], jnp.int32)  # ragged occupancy
    kpos = _dense_kpos(qpos, 96)
    ref = _oracle(q, k, v, qpos, kpos)
    pall = flash_decode(q, k, v, qpos, kpos, block_k=32, interpret=True)
    xla = flash_decode_xla(q, k, v, qpos, kpos, block_k=32)
    unb = flash_decode_xla(q, k, v, qpos, kpos, block_k=32, bounded=False)
    for got in (pall, xla, unb):
        np.testing.assert_allclose(np.asarray(got), ref, atol=2e-5)


def test_flash_decode_parity_window():
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, b=2, s=64, h=4, kh=2, dk=8)
    qpos = jnp.asarray([20, 63], jnp.int32)
    kpos = _dense_kpos(qpos, 64)
    ref = _oracle(q, k, v, qpos, kpos, window=16)
    pall = flash_decode(q, k, v, qpos, kpos, window=16, block_k=16,
                        interpret=True)
    xla = flash_decode_xla(q, k, v, qpos, kpos, window=16, block_k=16)
    np.testing.assert_allclose(np.asarray(pall), ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), ref, atol=2e-5)


def test_flash_decode_parity_ring_positions():
    """Ring-buffer caches hand the kernel permuted, non-monotonic positions
    with negatives for not-yet-written slots — the mask must not assume
    slot index == position (and XLA must run unbounded)."""
    rng = np.random.default_rng(2)
    s = 32
    q, k, v = _rand_qkv(rng, b=2, s=s, h=2, kh=2, dk=8)
    t = np.asarray([45, 7])  # tokens seen so far per slot
    kpos = np.empty((2, s), np.int32)
    for bi in range(2):
        j = np.arange(s)
        kpos[bi] = t[bi] - 1 - ((t[bi] - 1 - j) % s)  # ring layout
    kpos = jnp.asarray(kpos)
    qpos = jnp.asarray(t - 1, jnp.int32)
    ref = _oracle(q, k, v, qpos, kpos, window=s)
    pall = flash_decode(q, k, v, qpos, kpos, window=s, block_k=16,
                        interpret=True)
    xla = flash_decode_xla(q, k, v, qpos, kpos, window=s, block_k=16,
                           bounded=False)
    np.testing.assert_allclose(np.asarray(pall), ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), ref, atol=2e-5)


def test_flash_decode_padded_and_empty_slots():
    """Inactive batch lanes (all positions invalid) must come out exactly
    zero, not NaN — the online softmax divides by max(l, eps)."""
    rng = np.random.default_rng(3)
    q, k, v = _rand_qkv(rng, b=3, s=32, h=2, kh=1, dk=8)
    qpos = jnp.asarray([10, 0, 0], jnp.int32)
    kpos = np.array(_dense_kpos(qpos, 32))
    kpos[1:] = -1  # lanes 1, 2 inactive: nothing valid
    kpos = jnp.asarray(kpos)
    for fn in (lambda: flash_decode(q, k, v, qpos, kpos, block_k=16,
                                    interpret=True),
               lambda: flash_decode_xla(q, k, v, qpos, kpos, block_k=16)):
        got = np.asarray(fn())
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got[1:], 0.0)
        ref = _oracle(q, k, v, qpos, kpos)
        np.testing.assert_allclose(got, ref, atol=2e-5)


# ---------------------------------------------------------------------------
# kernel parity: paged flash-decode
# ---------------------------------------------------------------------------

def test_flash_decode_paged_parity():
    rng = np.random.default_rng(4)
    b, kh, h, dk, ps, pps, npg = 3, 2, 4, 8, 8, 4, 16
    pool_k = jnp.asarray(rng.standard_normal((npg, kh, ps, dk)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((npg, kh, ps, dk)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, dk)), jnp.float32)
    qpos = jnp.asarray([5, 20, 30], jnp.int32)
    # bind a logical prefix of pages per slot (unique physical pages > 0),
    # leave the rest unbound (-1); slot 0 fits in one page
    table = np.full((b, pps), -1, np.int32)
    free = list(range(1, npg))
    for bi in range(b):
        for li in range((int(qpos[bi]) // ps) + 1):
            table[bi, li] = free.pop()
    table = jnp.asarray(table)
    # oracle over the gathered logical view
    gk = np.asarray(pool_k)[np.maximum(np.asarray(table), 0)]
    gv = np.asarray(pool_v)[np.maximum(np.asarray(table), 0)]
    gk = gk.transpose(0, 1, 3, 2, 4).reshape(b, pps * ps, kh, dk)
    gv = gv.transpose(0, 1, 3, 2, 4).reshape(b, pps * ps, kh, dk)
    lpos = np.tile(np.arange(pps * ps, dtype=np.int32), (b, 1))
    lpos = np.where(np.asarray(table)[:, lpos[0] // ps] >= 0, lpos, -1)
    ref = _oracle(q, gk, gv, qpos, lpos)
    pall = flash_decode_paged(q, pool_k, pool_v, qpos, table, interpret=True)
    xla = flash_decode_paged_xla(q, pool_k, pool_v, qpos, table)
    np.testing.assert_allclose(np.asarray(pall), ref, atol=2e-5)
    np.testing.assert_allclose(np.asarray(xla), ref, atol=2e-5)


@pytest.mark.parametrize("window", [None, 12])
def test_flash_decode_paged_stacked_reads_its_layer(window):
    """A stacked (L, P, K, page, D) pool read at layer l gives exactly what
    the single-layer pool ``pool[l]`` gives, unbound (-1) table entries and
    a window included, for the Pallas kernel and the XLA lowering alike."""
    rng = np.random.default_rng(11)
    n_layers, b, kh, h, dk, ps, pps, npg = 3, 3, 2, 4, 8, 8, 4, 16
    stack_k = jnp.asarray(rng.standard_normal((n_layers, npg, kh, ps, dk)),
                          jnp.float32)
    stack_v = jnp.asarray(rng.standard_normal((n_layers, npg, kh, ps, dk)),
                          jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, h, dk)), jnp.float32)
    qpos = jnp.asarray([5, 20, 30], jnp.int32)
    table = jnp.asarray([[3, -1, -1, -1],
                         [7, 2, 9, -1],
                         [1, 4, 12, 15]], jnp.int32)
    for layer in range(n_layers):
        one = flash_decode_paged(q, stack_k[layer], stack_v[layer], qpos,
                                 table, window=window, interpret=True)
        stacked = flash_decode_paged(q, stack_k, stack_v, qpos, table,
                                     jnp.int32(layer), window=window,
                                     interpret=True)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(one))
        one = flash_decode_paged_xla(q, stack_k[layer], stack_v[layer], qpos,
                                     table, window=window)
        stacked = flash_decode_paged_xla(q, stack_k, stack_v, qpos, table,
                                         jnp.int32(layer), window=window)
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(one))


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------

def test_page_allocator_bind_free_reuse():
    al = PageAllocator(n_pages=9, page_size=4, n_slots=2, pages_per_slot=4)
    assert al.capacity == 8 and al.free_pages == 8
    for pos in range(0, 16, 4):
        assert al.ensure(0, pos)
        assert al.ensure(0, pos)  # idempotent re-bind
        al.check()
    assert al.used_pages == 4 and al.free_pages == 4
    assert al.ensure(1, 0) and al.ensure(1, 4)
    al.check()
    freed = al.release(0)
    assert freed == 4 and al.free_pages == 6
    al.check()
    # released pages are reusable; exhaustion reports False, mutates nothing
    for pos in range(0, 16, 4):
        assert al.ensure(0, pos)
    assert al.ensure(1, 8) and al.ensure(1, 12)
    assert al.free_pages == 0
    al.check()
    assert al.release(1) == 4 and al.free_pages == 4
    al.check()


def test_page_allocator_exhaustion_and_trash_page():
    al = PageAllocator(n_pages=3, page_size=4, n_slots=2, pages_per_slot=2)
    assert al.ensure(0, 0) and al.ensure(0, 4)
    assert not al.ensure(1, 0)  # exhausted
    assert al.table[1, 0] == -1  # nothing half-bound
    assert 0 not in al.table[al.table >= 0]  # trash page never handed out
    al.check()
    al.release(0)
    assert al.ensure(1, 0)
    al.check()
    with pytest.raises(ValueError):
        PageAllocator(n_pages=1, page_size=4, n_slots=1, pages_per_slot=1)


def test_page_allocator_position_past_table_width():
    al = PageAllocator(n_pages=5, page_size=4, n_slots=1, pages_per_slot=2)
    assert al.ensure(0, 7)
    assert not al.ensure(0, 8)  # past the table: reports False, no IndexError
    assert al.table[0, 1] >= 0  # in-range bindings untouched
    al.check()


# ---------------------------------------------------------------------------
# engine: paged cache + flash kernel parity, eviction/resume
# ---------------------------------------------------------------------------

def _drain_and_check(cfg, params, eng, reqs):
    out = eng.run_until_drained(list(reqs))
    assert set(out) == {rid for rid, _, _ in reqs}
    for rid, prompt, n in reqs:
        assert out[rid] == _greedy_reference(cfg, params, prompt, n), rid


def test_paged_engine_matches_reference(small_model):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16)
    rng = np.random.RandomState(3)
    reqs = [(f"p{i}", list(rng.randint(0, cfg.vocab_size, 4 + 2 * i)), 4)
            for i in range(4)]
    _drain_and_check(cfg, params, eng, reqs)
    assert eng.allocator.used_pages == 0  # all pages returned
    eng.allocator.check()


def test_flash_engine_matches_reference(small_model):
    cfg, params = small_model
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64,
                      decode_kernel="flash")
    rng = np.random.RandomState(4)
    reqs = [(f"f{i}", list(rng.randint(0, cfg.vocab_size, 5 + i)), 4)
            for i in range(3)]
    _drain_and_check(cfg, params, eng, reqs)


def test_flash_paged_engine_hybrid_arch():
    """gemma3 mixes ring local layers (dense flash path, unbounded) with
    global attention layers (paged flash path) in one stack."""
    cfg = smoke_config("gemma3_1b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(2),
                         jnp.dtype(cfg.dtype))
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16, decode_kernel="flash")
    rng = np.random.RandomState(5)
    reqs = [(f"g{i}", list(rng.randint(0, cfg.vocab_size, 6 + 3 * i)), 4)
            for i in range(3)]
    _drain_and_check(cfg, params, eng, reqs)


def test_flash_paged_engine_pages_every_layer():
    """With max_len inside gemma3's window every attention layer is paged:
    three pools ride each period's scan carry, stacked, and the two tail
    layers keep single-layer pools; both read and write in place."""
    cfg = smoke_config("gemma3_1b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(2),
                         jnp.dtype(cfg.dtype))
    eng = ServeEngine(cfg, params, n_slots=2, max_len=cfg.window_size,
                      paged=True, page_size=8, decode_kernel="flash",
                      kernel_interpret=True)
    assert all("pool_k" in c for c in eng.caches["periods"].values())
    assert all(c["pool_k"].ndim == 4 for c in eng.caches["tail"].values())
    rng = np.random.RandomState(5)
    reqs = [(f"g{i}", list(rng.randint(0, cfg.vocab_size, 5 + 3 * i)), 4)
            for i in range(3)]
    _drain_and_check(cfg, params, eng, reqs)


def test_flash_engine_recurrent_arch():
    """recurrentgemma: RG-LRU state must be zeroed on (lazy) admission while
    the ring KV rides the flash kernel's permuted-position path."""
    cfg = smoke_config("recurrentgemma_2b")
    params = init_params(model_spec(cfg), jax.random.PRNGKey(3),
                         jnp.dtype(cfg.dtype))
    eng = ServeEngine(cfg, params, n_slots=2, max_len=96,
                      decode_kernel="flash")
    rng = np.random.RandomState(6)
    reqs = [(f"r{i}", list(rng.randint(0, cfg.vocab_size, 5 + i)), 4)
            for i in range(4)]  # > n_slots: slot reuse must reset state
    _drain_and_check(cfg, params, eng, reqs)


def test_evict_and_resume_mid_generation(small_model):
    """Evicting a request mid-generation and re-admitting it (on a paged
    engine) must reproduce the uninterrupted greedy decode exactly."""
    cfg, params = small_model
    rng = np.random.RandomState(7)
    prompt = list(rng.randint(0, cfg.vocab_size, 6))
    other = list(rng.randint(0, cfg.vocab_size, 4))
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16)
    assert eng.add_request("victim", prompt, max_new=8)
    assert eng.add_request("other", other, max_new=10)
    done = {}
    for _ in range(len(prompt) + 3):  # victim is 3 tokens into generation
        done.update(eng.step())
    state = eng.evict("victim")
    assert state is not None and state["prompt"] == prompt
    assert 0 < len(state["tokens"]) < 8
    eng.allocator.check()
    # slot + pages freed: a new request can take its place immediately
    assert eng.add_request("victim", state["prompt"], state["max_new"],
                          resume_tokens=state["tokens"])
    while eng._active():
        done.update(eng.step())
    assert done["victim"] == _greedy_reference(cfg, params, prompt, 8)
    assert done["other"] == _greedy_reference(cfg, params, other, 10)


@pytest.mark.parametrize("arch", ["stablelm_1_6b", "gemma3_1b",
                                  "recurrentgemma_2b"])
def test_stalled_slot_resumes_uncorrupted(arch):
    """Page-pool exhaustion stalls one slot while the other keeps stepping.
    The stalled slot still rides the jitted step as a garbage lane — its
    bound pages, ring KV, and recurrent state must not advance on it, so
    once pages free up it resumes bit-exact against the uninterrupted
    greedy decode, on the step that donates the caches it reads (the
    stalled lanes are snapshot before dispatch)."""
    cfg = smoke_config(arch)
    params = init_params(model_spec(cfg), jax.random.PRNGKey(5),
                         jnp.dtype(cfg.dtype))
    # 3 usable pages, two requests needing 2 pages each: the slot that
    # loses the race for the third page stalls mid-generation until the
    # winner completes and releases its pages.
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16, n_pages=4)
    rng = np.random.RandomState(9)
    reqs = [("a", list(rng.randint(0, cfg.vocab_size, 4)), 20),
            ("b", list(rng.randint(0, cfg.vocab_size, 4)), 20)]
    for req in reqs:
        assert eng.add_request(*req)
    done, stalls = {}, 0
    for _ in range(200):
        before = {i: eng.slots[i].position for i in eng._active()}
        # a pending reset replaces recurrent lanes before the step reads them
        resetting, steps = bool(eng._pending_reset), eng.steps
        read = [c for p, c in jax.tree_util.tree_leaves_with_path(eng.caches)
                if not resetting or p[-1].key in ("k", "v", "pool_k", "pool_v")]
        done.update(eng.step())
        if eng.steps > steps:  # the step donated the caches it read
            assert read and all(c.is_deleted() for c in read)
        stalls += sum(1 for i, p in before.items()
                      if not eng.slots[i].done
                      and eng.slots[i].position == p)
        if not eng._active():
            break
    assert stalls > 0  # the scenario really exercised a stall
    eng.allocator.check()
    assert eng.allocator.used_pages == 0
    for rid, prompt, n in reqs:
        assert done[rid] == _greedy_reference(cfg, params, prompt, n), rid


def test_reset_full_defers_under_inflight_step(small_model):
    """reset_full admissions landing while a step's device call is in
    flight must defer their zero to the next assembly — an eager reset
    would be clobbered by the apply phase's ``self.caches = new_caches``,
    leaking the previous occupant's state into the new request."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, n_slots=2, max_len=32,
                      admission="reset_full")
    assert eng._step_guard.acquire(blocking=False)  # simulate in-flight step
    try:
        assert eng.add_request("r0", [1, 2, 3], max_new=3)
        assert 0 in eng._pending_reset  # deferred, not eagerly applied
    finally:
        eng._step_guard.release()
    assert eng.add_request("r1", [4, 5], max_new=3)
    assert 1 not in eng._pending_reset  # no step in flight: eager baseline
    done = {}
    while eng._active():
        done.update(eng.step())
    assert done["r0"] == _greedy_reference(cfg, params, [1, 2, 3], 3)
    assert done["r1"] == _greedy_reference(cfg, params, [4, 5], 3)


def test_reset_full_rejects_paged(small_model):
    """The full-lane zero indexes pool leaves by physical page, not slot —
    the combination would wipe other requests' KV and must not construct."""
    cfg, params = small_model
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, n_slots=2, max_len=32, paged=True,
                    admission="reset_full")


def test_oversized_prompt_rejected(small_model):
    """A prompt that can never fit max_len must fail loudly at submission
    (engine and router), not walk positions past the page table in the
    driver thread."""
    cfg, params = small_model
    eng = ServeEngine(cfg, params, n_slots=1, max_len=16, paged=True,
                      page_size=8)
    with pytest.raises(ValueError):
        eng.add_request("big", list(range(16)), max_new=4)
    with pytest.raises(ValueError):
        eng.add_request("big", list(range(10)), max_new=4,
                        resume_tokens=list(range(6)))
    assert eng.add_request("fits", list(range(15)), max_new=4)
    rs = ServeReplicaSet(cfg, params, n_replicas=1,
                         engine_kw=dict(n_slots=1, max_len=16))
    with pytest.raises(ValueError):
        rs.submit("big", list(range(16)))
    assert rs.lost == 0 and rs.submitted == 0


# ---------------------------------------------------------------------------
# replica set: routing, SLO admission, accounting
# ---------------------------------------------------------------------------

def test_replica_set_completes_all_zero_lost(small_model):
    cfg, params = small_model
    reg = MetricsRegistry()
    rs = ServeReplicaSet(cfg, params, n_replicas=2, registry=reg,
                         engine_kw=dict(n_slots=2, max_len=64, paged=True,
                                        page_size=16))
    rng = np.random.RandomState(8)
    prompts = [list(rng.randint(0, cfg.vocab_size, 4 + i)) for i in range(8)]
    with rs:
        pend = [rs.submit(f"q{i}", p, max_new=5)
                for i, p in enumerate(prompts)]
        assert rs.drain(timeout=120)
    assert rs.completed == 8 and rs.lost == 0 and rs.duplicates == 0
    assert sorted({p.replica for p in pend}) == [0, 1]  # both replicas used
    for p, prompt in zip(pend, prompts):
        assert p.tokens == _greedy_reference(cfg, params, prompt, 5)
    # the engines published their token counters under distinct replica labels
    fam = register_serve_metrics(reg)["tokens"]
    vals = {key[0]: child.value for key, child in fam.items()}
    assert vals.get("r0", 0) + vals.get("r1", 0) >= 8 * 5


def test_replica_set_sheds_on_ttft_violation(small_model):
    cfg, params = small_model
    rs = ServeReplicaSet(cfg, params, n_replicas=1,
                         engine_kw=dict(n_slots=1, max_len=64,
                                        step_latency_s=0.02),
                         ttft_slo=ttft_slo(0.001), on_violation="shed")
    with rs:
        warm = rs.submit("warm", [2, 3], max_new=4)
        assert warm.wait(60)  # rate signal is live; admission is no longer
        burst = [rs.submit(f"b{i}", [2, 3], max_new=8)  # cold-optimistic
                 for i in range(8)]
        assert rs.drain(timeout=120)
    assert rs.shed > 0
    assert rs.lost == 0
    shed = [p for p in burst if p.status == "shed"]
    assert all(p.resolved and p.tokens is None for p in shed)


def test_replica_set_spills_to_callback(small_model):
    cfg, params = small_model
    spilled = []
    rs = ServeReplicaSet(cfg, params, n_replicas=1,
                         engine_kw=dict(n_slots=1, max_len=64,
                                        step_latency_s=0.02),
                         ttft_slo=ttft_slo(0.001), on_violation="spill",
                         spill_to=spilled.append)
    with rs:
        warm = rs.submit("warm", [2, 3], max_new=4)
        assert warm.wait(60)
        for i in range(8):
            rs.submit(f"b{i}", [2, 3], max_new=8)
        assert rs.drain(timeout=120)
    assert rs.spilled == len(spilled) > 0
    assert rs.lost == 0


def test_replica_set_cluster_deploy(small_model):
    """Replica drivers as long-lived tasks on a serve-tainted pool, load
    driven by serve_loadgen tasks on the plain cpu pool."""
    from repro.cluster import KsaCluster
    from repro.core.scheduling import ResourceClassPolicy
    from repro.serve import ServeLoadGenComputing

    cfg, params = small_model
    rs = ServeReplicaSet(cfg, params, n_replicas=2,
                         engine_kw=dict(n_slots=2, max_len=64))
    cluster = KsaCluster(workers=1, prefix="tserve",
                         placement=ResourceClassPolicy(
                             extra_classes=("serve",)))
    with cluster:
        ids = rs.deploy(cluster, taint="serve")
        ServeLoadGenComputing.replica_set = rs
        gen = [cluster.submit("serve_loadgen",
                              params={"client": f"c{i}", "n_requests": 3,
                                      "prompt_len": 4, "max_new": 5,
                                      "vocab_size": cfg.vocab_size})
               for i in range(2)]
        assert cluster.wait_all(gen, timeout=120)
        results = [cluster.result(t) for t in gen]
        rs.stop()
        for t in ids:  # driver tasks completed cleanly with engine stats
            entry = cluster.task(t)
            assert entry.status == "DONE" and entry.result["steps"] >= 0
    assert all(r["completed"] == 3 and r["timed_out"] == 0 for r in results)
    assert rs.submitted == 6 and rs.lost == 0 and rs.duplicates == 0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_serve_metrics_registered_and_exported(small_model):
    cfg, params = small_model
    reg = MetricsRegistry()
    fams = register_serve_metrics(reg)
    assert set(fams) == {"queue_wait", "ttft", "step", "tokens", "requests",
                         "slots_active", "slots_total", "pages_used",
                         "pages_total"}
    assert register_serve_metrics(reg) is not None  # idempotent
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16, registry=reg, replica="r9")
    out = eng.run_until_drained([("m0", [1, 2, 3], 3)])
    assert out["m0"]
    text = reg.render()
    for name in ("ksa_serve_queue_wait_seconds", "ksa_serve_ttft_seconds",
                 "ksa_serve_step_seconds", "ksa_serve_tokens_total",
                 "ksa_serve_requests_total", "ksa_serve_slots_active",
                 "ksa_serve_slots_total", "ksa_serve_pages_used",
                 "ksa_serve_pages_total"):
        assert f"# TYPE {name}" in text, name
    assert 'ksa_serve_tokens_total{replica="r9"} 3' in text
    assert 'event="admitted"' in text and 'event="completed"' in text


def _record_phases(monkeypatch, module) -> list:
    """Swap ``module``'s ``phase`` for one that lists (name, seconds)."""
    seen = []

    class _Recorded(phase):
        __slots__ = ()

        def __exit__(self, *exc):
            super().__exit__(*exc)
            seen.append((self.name, self.dur_s))

    monkeypatch.setattr(module, "phase", _Recorded)
    return seen


def test_one_step_records_each_engine_phase_once(small_model, monkeypatch):
    cfg, params = small_model
    reg = MetricsRegistry()
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, paged=True,
                      page_size=16, registry=reg, replica="r8")
    seen = _record_phases(monkeypatch, serve_engine)
    step = register_serve_metrics(reg)["step"].labels(replica="r8")
    assert eng.step() == []  # idle: no step, no phase
    assert step.count == 0 and seen == []
    assert eng.add_request("p0", [1, 2, 3], 2)
    eng.step()
    assert [n for n, _ in seen] == ["serve.assemble", "serve.dispatch",
                                    "serve.sync", "serve.apply"]
    # the step histogram keeps its meaning: dispatch plus sync
    dur = dict(seen)
    assert step.count == 1
    assert step.sum == pytest.approx(dur["serve.dispatch"]
                                     + dur["serve.sync"])


def test_an_idle_replica_poll_records_no_loop_phase(small_model,
                                                    monkeypatch):
    cfg, params = small_model
    rs = ServeReplicaSet(cfg, params, n_replicas=1,
                         engine_kw=dict(n_slots=2, max_len=64))
    seen = _record_phases(monkeypatch, serve_replica)
    assert rs._drive_once(0) is False and seen == []
    rs.submit("q0", [1, 2, 3], 2)
    assert rs._drive_once(0) is True
    assert [n for n, _ in seen] == ["serve.admit", "serve.loop"]
