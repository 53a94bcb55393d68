"""repro.serve: continuous-batching correctness and the serving acceptance
guards — admission/retirement order, bucket-reuse zero recompiles (same
style as tests/test_engine_service.py), equality with the sequential
batch-1 decode loop, and sharded-vs-single-device decode equality under 8
virtual devices (subprocess harness from conftest)."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_under_devices
from repro.configs import get_config
from repro.models.transformer import lm_decode_step, lm_init, make_cache
from repro.serve import (AdmissionFeeder, Request, RequestQueue, Scheduler,
                         ServeEngine)
from repro.serve.feeder import PreparedAdmission
from repro.serve.scheduler import NO_TOKEN

jax.config.update("jax_platform_name", "cpu")

CFG = get_config("gemma2-9b", smoke=True)
PARAMS = lm_init(CFG, jax.random.PRNGKey(0))


def _requests(n, rng, prompt_cap=8, gen_cap=6):
    return [(rng.integers(0, CFG.vocab,
                          int(rng.integers(1, prompt_cap + 1))).tolist(),
             int(rng.integers(1, gen_cap + 1))) for _ in range(n)]


def _sequential_reference(reqs, max_len=32):
    """Batch-1 teacher-forced prefill + greedy loop, one request at a time."""
    dec = jax.jit(lambda p, c, t, pos: lm_decode_step(CFG, p, c, t, pos))
    outs = []
    for prompt, max_new in reqs:
        cache = make_cache(CFG, batch=1, max_len=max_len)
        tok = None
        for i, t in enumerate(prompt):
            tok, cache = dec(PARAMS, cache, jnp.array([[t]], jnp.int32),
                             jnp.int32(i))
        out = [int(tok[0, 0])]
        for i in range(max_new - 1):
            tok, cache = dec(PARAMS, cache, tok,
                             jnp.int32(len(prompt) + i))
            out.append(int(tok[0, 0]))
        outs.append(out)
    return outs


# ------------------------------------------------------- end-to-end decode
def test_batched_serve_matches_sequential_loop():
    """Slot independence: every request's tokens are exactly what the
    batch-1 sequential loop produces, regardless of what its slot
    neighbours are doing (admission schedule does not leak into results)."""
    rng = np.random.default_rng(0)
    reqs = _requests(6, rng)
    eng = ServeEngine(CFG, PARAMS, n_slots=2, max_len=32, prompt_cap=8)
    for prompt, max_new in reqs:
        eng.submit(prompt, max_new)
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == len(reqs)
    want = _sequential_reference(reqs)
    for req in completed:
        assert req.tokens_out == want[req.rid], req.rid


# ----------------------------------------------------- admission/retirement
def test_admission_is_fifo_and_slots_fill_lowest_first():
    rng = np.random.default_rng(1)
    reqs = _requests(7, rng, gen_cap=4)
    eng = ServeEngine(CFG, PARAMS, n_slots=4, max_len=32, prompt_cap=8)
    handles = [eng.submit(p, g) for p, g in reqs]
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == len(reqs)
    # FIFO: admission times are monotone in submission order
    admits = [h.admit_t for h in handles]
    assert all(a is not None for a in admits)
    assert admits == sorted(admits)
    # the first wave seats in slot order 0..3 (lowest free slot first)
    assert [h.slot for h in handles[:4]] == [0, 1, 2, 3]


def test_retirement_frees_slots_for_later_requests():
    """More requests than slots: every request still completes, with its
    full generation budget, through slot reuse."""
    rng = np.random.default_rng(2)
    reqs = _requests(9, rng, gen_cap=5)
    eng = ServeEngine(CFG, PARAMS, n_slots=2, max_len=32, prompt_cap=8)
    for p, g in reqs:
        eng.submit(p, g)
    eng.close_submissions()
    completed = eng.run()
    assert sorted(r.rid for r in completed) == list(range(9))
    for r in completed:
        assert len(r.tokens_out) == reqs[r.rid][1]
        assert all(0 <= t < CFG.vocab for t in r.tokens_out)
    assert eng.stats.admitted == eng.stats.retired == 9


# -------------------------------------------------------- zero recompiles
def test_bucket_reuse_zero_recompiles_for_mixed_lengths():
    """The acceptance guard: after warmup, admitting requests of every
    (prompt_len, max_new) mix reuses the ONE compiled step program —
    admission writes rows into fixed pow2 buckets and never changes a
    traced shape (the serve analog of
    test_engine_service.test_service_zero_recompiles...)."""
    eng = ServeEngine(CFG, PARAMS, n_slots=4, max_len=32, prompt_cap=8)
    eng.submit([1, 2, 3], 2)  # warmup compile
    eng.close_submissions()
    eng.run()
    assert eng.step_cache_size() == 1
    rng = np.random.default_rng(3)
    eng.reopen()
    for p, g in _requests(8, rng):  # every length in [1, 8] x [1, 6]
        eng.submit(p, g)
    eng.close_submissions()
    completed = eng.run()
    assert len(completed) == 8
    assert eng.step_cache_size() == 1  # zero recompiles after warmup


# ------------------------------------------------------------------- eos
def test_eos_retires_early():
    # stop at the first *fresh* token value so the cut point is
    # unambiguous; the random-init model often repeats one token, so take
    # the first seeded prompt whose reference has a fresh token after the
    # first step
    rng = np.random.default_rng(4)
    for _ in range(16):
        prompt = rng.integers(0, CFG.vocab, 5).tolist()
        [ref] = _sequential_reference([(prompt, 6)])
        fresh = [i for i in range(1, len(ref)) if ref[i] not in ref[:i]]
        if fresh:
            break
    assert fresh, "no seeded prompt yields a fresh token to cut at"
    j = fresh[0]
    eng = ServeEngine(CFG, PARAMS, n_slots=2, max_len=32, prompt_cap=8,
                      eos_id=ref[j])
    eng.submit(prompt, 6)
    eng.close_submissions()
    [req] = eng.run()
    assert req.tokens_out == ref[:j]  # stopped at (and excluded) eos


# ------------------------------------------------- scheduler unit behavior
def _prep(rid, plen=3, max_new=2):
    req = Request(rid=rid, prompt=list(range(1, plen + 1)), max_new=max_new)
    return PreparedAdmission(req, np.zeros(8, np.int32), plen)


def test_scheduler_cooling_blocks_immediate_slot_reuse():
    """A retired slot must survive one more process() cycle before reuse:
    the step in flight at retirement can still emit a stale token for the
    old request, which must not be attributed to a new occupant."""
    s = Scheduler(n_slots=1)
    s.admit(_prep(0, max_new=1))
    finished = s.process(np.array([7]))  # emits its 1 budgeted token
    assert [r.rid for _, r in finished] == [0]
    assert not s.has_free_slot  # cooling: the in-flight step is unprocessed
    assert s.process(np.array([9])) == []  # stale token, ignored
    assert s.has_free_slot  # now safe to reuse
    slot = s.admit(_prep(1, max_new=2))
    assert slot == 0
    s.process(np.array([NO_TOKEN]))  # prefilling: nothing emitted
    assert s._slots[0].tokens_out == []
    s.process(np.array([4]))
    assert s._slots[0].tokens_out == [4]


def test_feeder_relays_producer_errors():
    """A producer-thread failure must surface out of poll(), never strand
    the engine loop waiting on a done flag that can no longer flip."""
    import pytest
    q = RequestQueue()
    q.put(Request(rid=0, prompt=["not-a-token"], max_new=1))  # bypasses
    q.close()                                   # ServeEngine.submit checks
    with AdmissionFeeder(q, prompt_cap=4, device_put=False) as feeder:
        with pytest.raises(ValueError):
            for _ in range(100):  # bounded: error lands within ~a poll
                assert feeder.poll(timeout=0.1) is None
                assert not feeder.done  # poll raises before done can flip


def test_feeder_prepares_fifo_and_signals_done():
    q = RequestQueue()
    for rid in range(3):
        q.put(Request(rid=rid, prompt=[rid + 1] * (rid + 1), max_new=1))
    q.close()
    with AdmissionFeeder(q, prompt_cap=4, device_put=False) as feeder:
        got = []
        while True:
            item = feeder.poll(timeout=1.0)
            if item is None:
                if feeder.done:
                    break
                continue
            got.append(item)
        assert [p.request.rid for p in got] == [0, 1, 2]
        assert [p.plen for p in got] == [1, 2, 3]
        np.testing.assert_array_equal(got[2].row, [3, 3, 3, 0])


# ---------------------------------------------------------- sharded decode
def test_sharded_serve_matches_single_device():
    """The mesh path (sequence-sharded slot cache + LSE-combined decode
    collective) serves the same tokens as the single-device engine."""
    out = run_under_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("data",))
        from repro.configs import get_config
        from repro.models.transformer import lm_init
        from repro.serve import ServeEngine

        cfg = get_config("gemma2-9b", smoke=True)
        params = lm_init(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        reqs = [(rng.integers(0, cfg.vocab,
                              int(rng.integers(1, 9))).tolist(),
                 int(rng.integers(1, 6))) for _ in range(5)]

        def serve(mesh):
            eng = ServeEngine(cfg, params, n_slots=2, max_len=64,
                              prompt_cap=8, mesh=mesh)
            for p, g in reqs:
                eng.submit(p, g)
            eng.close_submissions()
            done = eng.run()
            return {r.rid: r.tokens_out for r in done}

        single = serve(None)
        with jax.set_mesh(mesh):
            sharded = serve(mesh)
        assert single == sharded, (single, sharded)
        print("OK")
    """)
    assert "OK" in out
