"""Serving correctness: prefill/decode vs full forward; engine behaviour;
continuous-batching engine vs the fixed-slot path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.models.layers import ParallelCtx
from repro.models.model import Model
from repro.serve.engine import ServeEngine
from repro.serve.paged_kv import PageAllocator, PageGeometry
from repro.serve.scheduler import ContinuousServeEngine

CTX = ParallelCtx()


def _setup(arch, f32=False, **over):
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        over.setdefault("capacity_factor", 8.0)  # no drops -> comparable
    if f32:
        over["dtype"] = "float32"
    if over:
        cfg = cfg.with_(**over)
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _batch(cfg, rng, B, S):
    toks = jax.random.randint(rng, (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :S], "targets": toks[:, 1:]}
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(rng, (B, cfg.frontend_seq, 1024)) * 0.1
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(rng, (B, cfg.frontend_seq, 1024)) * 0.1
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_matches_forward(arch):
    cfg, m, params = _setup(arch)
    rng = jax.random.PRNGKey(1)
    batch = _batch(cfg, rng, 2, 12)
    full = m.forward(params, batch, CTX)[:, -1]
    extra = cfg.frontend_seq if cfg.family == "vlm" else 0
    lp, cache = m.prefill(params, batch, CTX, cache_n=16 + extra)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step_matches_forward(arch):
    cfg, m, params = _setup(arch)
    rng = jax.random.PRNGKey(2)
    batch = _batch(cfg, rng, 2, 12)
    extra = cfg.frontend_seq if cfg.family == "vlm" else 0
    lp, cache = m.prefill(params, batch, CTX, cache_n=16 + extra)
    nt = jnp.argmax(lp, -1).astype(jnp.int32)
    ld, cache2 = m.decode_step(params, nt, cache, CTX)
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], nt[:, None]], 1)
    full2 = m.forward(params, batch2, CTX)[:, -1]
    np.testing.assert_allclose(np.asarray(ld), np.asarray(full2),
                               atol=8e-2, rtol=8e-2)
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


def test_decode_exact_in_f32():
    """bf16 tolerance above is pure rounding: f32 must be near-exact."""
    cfg, m, params = _setup("h2o_danube_1_8b", f32=True)
    rng = jax.random.PRNGKey(3)
    batch = _batch(cfg, rng, 2, 12)
    lp, cache = m.prefill(params, batch, CTX, cache_n=16)
    nt = jnp.argmax(lp, -1).astype(jnp.int32)
    ld, _ = m.decode_step(params, nt, cache, CTX)
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], nt[:, None]], 1)
    full2 = m.forward(params, batch2, CTX)[:, -1]
    np.testing.assert_allclose(np.asarray(ld), np.asarray(full2), atol=2e-4)


def test_sliding_window_ring_cache():
    """SWA decode with a ring cache smaller than the generated length."""
    cfg, m, params = _setup("h2o_danube_1_8b", f32=True,
                            sliding_window=8)
    rng = jax.random.PRNGKey(4)
    B, S = 1, 12
    toks = jax.random.randint(rng, (B, S + 8), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :S]}
    lp, cache = m.prefill(params, batch, CTX, cache_n=S + 8)
    assert cache["layers"]["l0"]["k"].shape[1] == 8  # ring == window
    # decode 4 tokens; reference = full forward each time
    cur = toks[:, :S]
    tok = jnp.argmax(lp, -1).astype(jnp.int32)
    for _ in range(4):
        ld, cache = m.decode_step(params, tok, cache, CTX)
        cur = jnp.concatenate([cur, tok[:, None]], 1)
        ref = m.forward(params, {"tokens": cur}, CTX)[:, -1]
        np.testing.assert_allclose(np.asarray(ld), np.asarray(ref), atol=3e-4)
        tok = jnp.argmax(ld, -1).astype(jnp.int32)


def test_engine_generates_deterministically():
    cfg, m, params = _setup("minicpm_2b")
    eng = ServeEngine(m, params, CTX, cache_n=64)
    out1 = eng.generate([[1, 2, 3], [4, 5, 6, 7]], max_new=6)
    out2 = eng.generate([[1, 2, 3], [4, 5, 6, 7]], max_new=6)
    assert out1 == out2
    assert all(len(o) == 6 for o in out1)


def test_engine_never_samples_with_root_or_reused_key(monkeypatch):
    """Regression: generate() sampled the first token with the root
    PRNGKey and then split that same key in the decode loop — classic
    key reuse.  Every categorical draw must use a fresh split key."""
    cfg, m, params = _setup("minicpm_2b")
    eng = ServeEngine(m, params, CTX, cache_n=64, temperature=1.0)
    seen = []
    orig = jax.random.categorical

    def spy(key, *args, **kwargs):
        seen.append(np.asarray(key).tobytes())
        return orig(key, *args, **kwargs)

    monkeypatch.setattr(jax.random, "categorical", spy)
    eng.generate([[1, 2, 3]], max_new=4)
    assert len(seen) >= 2
    root = np.asarray(jax.random.PRNGKey(eng.seed)).tobytes()
    assert root not in seen          # the root key is only ever folded
    assert len(set(seen)) == len(seen)  # and no key is used twice


def test_engine_overflow_raises_value_error():
    cfg, m, params = _setup("minicpm_2b")
    eng = ServeEngine(m, params, CTX, cache_n=16)
    with pytest.raises(ValueError, match=r"12.*8.*20.*16"):
        eng.generate([[1] * 12], max_new=8)


def _first_new_token(tokens):
    """First index > 0 whose token does not occur earlier in ``tokens``."""
    return next(i for i in range(1, len(tokens))
                if tokens[i] not in tokens[:i])


def test_engine_stop_token_not_emitted():
    """Stop-token semantics: terminate the request *without* emitting."""
    cfg, m, params = _setup("minicpm_2b", f32=True)
    eng = ServeEngine(m, params, CTX, cache_n=64)
    free = eng.generate([[1, 2, 3]], max_new=6)[0]
    assert len(free) == 6
    # the first position whose token did not occur before it: an earlier
    # occurrence would (rightly) stop the request sooner
    i = _first_new_token(free)
    stop = free[i]
    out = eng.generate([[1, 2, 3]], max_new=6, stop_token=stop)[0]
    assert out == free[:i] and stop not in out
    # stop on the very first sampled token -> empty output
    out0 = eng.generate([[1, 2, 3]], max_new=6, stop_token=free[0])[0]
    assert out0 == []


# --------------------------------------------------------------------------
# continuous-batching engine (scheduler + paged KV)
# --------------------------------------------------------------------------

def _cont_setup(arch="minicpm_2b", **kw):
    cfg, m, params = _setup(arch, f32=True)
    return cfg, m, params


def test_page_allocator_invariants():
    geom = PageGeometry(page_size=8, n_pages=9, pages_per_slot=4)
    assert geom.usable_pages == 8 and geom.slot_capacity == 32
    assert geom.pages_for(1) == 1 and geom.pages_for(8) == 1
    assert geom.pages_for(9) == 2
    al = PageAllocator(geom)
    a = al.alloc(3)
    b = al.alloc(5)
    assert al.alloc(1) is None and al.n_free == 0
    assert 0 not in a + b  # scratch page never handed out
    al.free(a)
    with pytest.raises(ValueError):
        al.free(a)  # double free
    al.free(b)
    assert al.n_free == geom.usable_pages


def test_continuous_matches_fixed_slot_greedy():
    """Greedy outputs are identical to the fixed-slot path per request,
    across mixed prompt lengths, chunked prefill, and slot recycling."""
    cfg, m, params = _cont_setup()
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10], [11, 12], [13] * 9]
    ref = [ServeEngine(m, params, CTX, cache_n=32).generate([p], max_new=6)[0]
           for p in prompts]
    eng = ContinuousServeEngine(m, params, CTX, n_slots=2, max_len=32,
                                page_size=8, prefill_chunk=4)
    out = eng.generate(prompts, max_new=6)
    assert out == ref
    # decode and prefill each compiled exactly once across the whole run
    assert eng.trace_counts == {"decode": 1, "prefill": 1}


def test_page_free_list_restored_after_burst():
    """Leak invariant: a drained burst returns every page to the free
    list and clears every page-table row and slot."""
    cfg, m, params = _cont_setup()
    eng = ContinuousServeEngine(m, params, CTX, n_slots=2, max_len=32,
                                page_size=4, prefill_chunk=8)
    prompts = [[1 + i, 2 + i, 3 + i] for i in range(7)]
    outs = eng.generate(prompts, max_new=5)
    assert all(len(o) == 5 for o in outs)
    assert eng.alloc.n_free == eng.geom.usable_pages
    assert eng.alloc.n_live == 0
    assert not eng.pending and (eng.page_table == 0).all()


def test_admission_under_full_queue():
    """More requests than slots/pages: FCFS admission drains the queue
    as slots recycle; mid-flight the queue really is backed up."""
    cfg, m, params = _cont_setup()
    eng = ContinuousServeEngine(m, params, CTX, n_slots=2, max_len=16,
                                page_size=4, n_pages=9, prefill_chunk=4)
    rids = [eng.submit([1 + i, 2 + i], max_new=4) for i in range(6)]
    assert len(eng._queue) == 6  # nothing admitted before the first step
    got = {r: [] for r in rids}

    def drain(events):
        for ev in events:
            if ev.token is not None:
                got[ev.rid].append(ev.token)

    drain(eng.step())
    assert any(s is not None for s in eng._slots)
    assert len(eng._queue) >= 2  # only n_slots admitted so far
    while eng.pending:
        drain(eng.step())
    assert all(len(got[r]) == 4 for r in rids)


def test_continuous_stop_token_and_max_new_edges():
    cfg, m, params = _cont_setup()
    eng = ContinuousServeEngine(m, params, CTX, n_slots=2, max_len=16,
                                page_size=4, prefill_chunk=4)
    free = eng.generate([[1, 2, 3]], max_new=6)[0]
    assert len(free) == 6
    # stop token terminates without being emitted
    i = _first_new_token(free)
    out = eng.generate([[1, 2, 3]], max_new=6, stop_token=free[i])[0]
    assert out == free[:i] and free[i] not in out
    # stop on the first sampled token -> empty output, done event only
    evs = list(eng.stream([[1, 2, 3]], max_new=6, stop_token=free[0]))
    assert [e.token for e in evs] == [None] and evs[-1].done
    # max_new=1 emits exactly one token; exact capacity fit admits
    assert len(eng.generate([[1, 2, 3]], max_new=1)[0]) == 1
    assert len(eng.generate([[5] * 12], max_new=4)[0]) == 4  # 12+4 == 16
    # overflow raises with the offending numbers
    with pytest.raises(ValueError, match=r"13.*4.*17.*16"):
        eng.submit([5] * 13, max_new=4)
    assert eng.alloc.n_free == eng.geom.usable_pages


def test_continuous_sampling_independent_of_batch_composition():
    """fold_in(root, rid) keys: a request's sampled tokens don't depend
    on which requests co-reside in the batch."""
    cfg, m, params = _cont_setup()

    def run(prompts):
        eng = ContinuousServeEngine(m, params, CTX, n_slots=4, max_len=32,
                                    page_size=8, prefill_chunk=4,
                                    temperature=1.0, seed=7)
        return eng.generate(prompts, max_new=5)

    alone = run([[1, 2, 3]])[0]
    crowded = run([[1, 2, 3], [9, 8, 7, 6], [4, 4, 4, 4, 4, 4]])[0]
    assert alone == crowded


def test_continuous_rejects_stateful_families():
    cfg, m, params = _setup("xlstm_350m")
    with pytest.raises(ValueError, match="dense/moe"):
        ContinuousServeEngine(m, params, CTX)
