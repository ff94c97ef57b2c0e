"""Paged KV cache: specs, the host-side page allocator, gather/commit
round-trips, scheduler admission, and the continuous-batching engine
against the whole-batch ``generate`` reference."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models.kvcache import (OutOfPagesError, PagedCacheConfig,
                                  PageAllocator, attn_cache_spec,
                                  commit_prefill, gather_pages,
                                  paged_attn_cache_spec, ssm_cache_spec)
from repro.serve import SERVE_MODES, Request, Scheduler, ServeEngine


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("llama3.2-3b"), layers=2, d_model=32)
    from repro.models.model import build_model
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# -- dense specs -----------------------------------------------------------


def test_dense_cache_specs():
    cfg = reduced(get_config("llama3.2-3b"))
    spec = attn_cache_spec(cfg, 3, 16, jnp.bfloat16)
    assert spec["k"].shape == (3, 16, cfg.num_kv_heads, cfg.head_dim)
    assert spec["v"].dtype == jnp.bfloat16

    mcfg = reduced(get_config("mamba2-130m"))
    sspec = ssm_cache_spec(mcfg, 2, jnp.float32)
    assert sspec["conv_x"].shape[0] == 2
    assert sspec["conv_x"].shape[1] == mcfg.ssm_conv - 1
    assert sspec["state"].dtype == jnp.float32  # SSD state stays f32


def test_paged_spec_shapes():
    cfg = reduced(get_config("llama3.2-3b"))
    pcfg = PagedCacheConfig(page_size=4, num_pages=10, max_slots=2,
                            max_seq=13)
    spec = paged_attn_cache_spec(cfg, pcfg, jnp.bfloat16)
    assert spec["k_pages"].shape == (10, 4, cfg.num_kv_heads, cfg.head_dim)
    assert spec["v_pages"].dtype == jnp.bfloat16
    assert pcfg.pages_per_slot == 4  # ceil(13 / 4)


def test_paged_config_validation():
    with pytest.raises(ValueError):
        PagedCacheConfig(page_size=0, num_pages=8, max_slots=2, max_seq=8)
    with pytest.raises(ValueError):
        PagedCacheConfig(page_size=4, num_pages=8, max_slots=-1, max_seq=8)


# -- allocator -------------------------------------------------------------


def _pcfg(**kw):
    base = dict(page_size=4, num_pages=8, max_slots=3, max_seq=16)
    base.update(kw)
    return PagedCacheConfig(**base)


def test_allocate_append_release_roundtrip():
    alloc = PageAllocator(_pcfg())
    s = alloc.allocate(10)  # 3 pages
    assert alloc.free_page_count == 5
    row = alloc.block_table[s]
    assert (row[:3] < 8).all() and (row[3:] == 8).all()  # sentinel tail
    alloc.commit(s, 6)
    assert alloc.seq_lens[s] == 6
    for _ in range(4):
        alloc.append(s)
    assert alloc.seq_lens[s] == 10
    # reserved capacity is 3 pages = 12 tokens: 2 more appends fit, not 3
    alloc.append(s, 2)
    with pytest.raises(OutOfPagesError):
        alloc.append(s)
    alloc.release(s)
    assert alloc.free_page_count == 8 and alloc.free_slot_count == 3
    assert (alloc.block_table[s] == 8).all()
    assert alloc.seq_lens[s] == 0


def test_device_tables_do_not_follow_later_host_changes():
    """A step's tables stay what they were when taken, whatever the
    allocator does to its host arrays afterwards."""
    alloc = PageAllocator(_pcfg())
    s = alloc.allocate(10)
    alloc.commit(s, 6)
    bt, ln = alloc.device_tables()
    before = (np.array(bt), np.array(ln))
    alloc.append(s, 2)
    alloc.release(s)
    assert (np.asarray(bt) == before[0]).all()
    assert (np.asarray(ln) == before[1]).all() and int(ln[s]) == 6


def test_allocator_exhaustion_and_recycle():
    alloc = PageAllocator(_pcfg())  # 8 pages
    a = alloc.allocate(16)  # 4 pages
    b = alloc.allocate(16)  # 4 pages -> pool empty
    assert not alloc.can_allocate(4)
    with pytest.raises(OutOfPagesError):
        alloc.allocate(4)
    alloc.release(a)
    assert alloc.can_allocate(16)
    c = alloc.allocate(16)
    assert c != b  # a's recycled pages back the new slot
    assert alloc.free_page_count == 0
    alloc.release(b), alloc.release(c)
    # all three slots busy -> no slot even though pages are free
    s = [alloc.allocate(4) for _ in range(3)]
    assert not alloc.can_allocate(4)
    with pytest.raises(OutOfPagesError):
        alloc.allocate(4)
    for x in s:
        alloc.release(x)


def test_allocate_validates_max_seq():
    alloc = PageAllocator(_pcfg())
    with pytest.raises(ValueError):
        alloc.allocate(17)  # > max_seq
    with pytest.raises(ValueError):
        alloc.allocate(0)
    s = alloc.allocate(4)
    with pytest.raises(ValueError):
        alloc.commit(s, 5)  # past the single reserved page


# -- gather / commit -------------------------------------------------------


def test_gather_pages_roundtrip():
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.standard_normal((8, 4, 2, 3)), jnp.float32)
    bt = jnp.asarray([[5, 1, 8, 8], [0, 8, 8, 8]], jnp.int32)
    g = gather_pages(pages, bt)
    assert g.shape == (2, 16, 2, 3)
    np.testing.assert_array_equal(np.asarray(g[0, :4]), np.asarray(pages[5]))
    np.testing.assert_array_equal(np.asarray(g[0, 4:8]), np.asarray(pages[1]))
    np.testing.assert_array_equal(np.asarray(g[1, :4]), np.asarray(pages[0]))


def test_commit_prefill_roundtrip(setup):
    cfg, model, params = setup
    pcfg = _pcfg(max_seq=12)
    alloc = PageAllocator(pcfg)
    slot = alloc.allocate(9)
    from repro.models import transformer as T
    pages = T.init_paged_cache(cfg, pcfg, jnp.float32)

    rng = np.random.default_rng(1)
    S0, Spad = 6, 8  # prefill padded past the true length
    dense = model.init_cache(1, Spad, jnp.float32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, Spad)), jnp.int32)
    from repro.train.serve import make_prefill_step
    _, dense = make_prefill_step(model, None)(params, {"tokens": toks}, dense)

    out = commit_prefill(pages["layers"], dense["layers"],
                         jnp.asarray(alloc.block_table[slot]), S0,
                         page_size=pcfg.page_size)
    for name, stacked in out.items():
        g = gather_pages(stacked["k_pages"][0],
                         jnp.asarray(alloc.block_table[slot])[None])
        ref = np.asarray(dense["layers"][name]["k"][0, 0])
        np.testing.assert_allclose(np.asarray(g[0, :S0]), ref[:S0])
        # pad positions (>= S0) dropped on the sentinel, pages stay zero
        np.testing.assert_array_equal(np.asarray(g[0, S0:]), 0.0)


# -- scheduler -------------------------------------------------------------


def test_scheduler_budget_and_admission():
    alloc = PageAllocator(PagedCacheConfig(page_size=4, num_pages=32,
                                           max_slots=4, max_seq=24))
    sched = Scheduler(alloc, prefill_token_budget=10)
    for rid, plen in enumerate((6, 6, 6)):
        sched.submit(Request(rid=rid,
                             prompt=np.zeros((plen,), np.int32),
                             max_new_tokens=4))
    first = sched.admit()
    # 6 + 6 > 10: the second admission waits for the next step
    assert [r.rid for r in first] == [0, 1] or [r.rid for r in first] == [0]
    assert sum(r.prompt_len for r in first) <= 10 + first[-1].prompt_len
    second = sched.admit()
    assert {r.rid for r in first} | {r.rid for r in second} >= {0, 1}


def test_scheduler_oversized_head_admitted_alone():
    """A prompt longer than the budget must not starve at the head."""
    alloc = PageAllocator(PagedCacheConfig(page_size=4, num_pages=32,
                                           max_slots=4, max_seq=24))
    sched = Scheduler(alloc, prefill_token_budget=4)
    sched.submit(Request(rid=0, prompt=np.zeros((12,), np.int32),
                         max_new_tokens=4))
    admitted = sched.admit()
    assert [r.rid for r in admitted] == [0]


def test_scheduler_rejects_over_max_seq():
    alloc = PageAllocator(_pcfg())
    sched = Scheduler(alloc)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=np.zeros((15,), np.int32),
                             max_new_tokens=4))  # 19 > max_seq=16


def test_scheduler_slot_recycling():
    alloc = PageAllocator(PagedCacheConfig(page_size=4, num_pages=8,
                                           max_slots=1, max_seq=16))
    sched = Scheduler(alloc)
    for rid in range(2):
        sched.submit(Request(rid=rid, prompt=np.zeros((4,), np.int32),
                             max_new_tokens=4))
    (a,) = sched.admit()
    assert sched.admit() == []  # single slot busy
    sched.finish(a, "max_new")
    assert a.done and a.finish_reason == "max_new" and a.slot is None
    (b,) = sched.admit()
    assert b.rid == 1 and b.slot == 0  # recycled


# -- engine ----------------------------------------------------------------


def test_serve_engine_matches_generate(setup):
    """Continuous batching (shared pool, slot churn, mixed steps) must be
    token-exact against the whole-batch dense reference."""
    from repro.train.serve import generate
    cfg, model, params = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in (5, 9, 3, 12)]
    max_new = 5

    pcfg = PagedCacheConfig(page_size=4, num_pages=16, max_slots=2,
                            max_seq=32)
    eng = ServeEngine(model, params, pcfg, prefill_token_budget=12)
    out, stats = eng.run(prompts, max_new_tokens=max_new, collect_stats=True)

    assert max(s["active"] for s in stats) <= 2  # never beyond the slots
    for rid, prompt in enumerate(prompts):
        ref = generate(model, params, jnp.asarray(prompt[None]),
                       max_new_tokens=max_new)
        np.testing.assert_array_equal(np.asarray(ref[0]), out[rid])


def test_serve_engine_eos_recycles_early(setup):
    cfg, model, params = setup
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=(6,)).astype(np.int32)
    pcfg = PagedCacheConfig(page_size=4, num_pages=8, max_slots=1,
                            max_seq=16)
    free = ServeEngine(model, params, pcfg).run([prompt], max_new_tokens=6)
    eos = int(free[0][7])  # the 2nd generated token

    eng = ServeEngine(model, params, pcfg, eos_id=eos)
    rid = eng.submit(prompt, max_new_tokens=6)
    out = eng.run()
    assert out[rid].shape[0] < prompt.shape[0] + 6  # stopped at EOS
    assert out[rid][-1] == eos
    assert eng.alloc.free_slot_count == 1  # slot recycled


def test_serve_engine_rejects_impossible_request(setup):
    cfg, model, params = setup
    pcfg = PagedCacheConfig(page_size=4, num_pages=2, max_slots=1,
                            max_seq=16)  # pool of 8 tokens
    eng = ServeEngine(model, params, pcfg)
    # a request whose worst-case reservation exceeds the whole pool is
    # refused at submit() — it could never be admitted, even idle
    with pytest.raises(OutOfPagesError, match="never be admitted"):
        eng.submit(np.zeros((8,), np.int32), max_new_tokens=4)  # needs 12
    assert not eng.scheduler.has_work


def test_serve_engine_idle_pool_raise_via_scheduler_bypass(setup):
    # the step()-time guard still fires for requests that skip the
    # engine's submit() validation (direct scheduler use)
    cfg, model, params = setup
    pcfg = PagedCacheConfig(page_size=4, num_pages=2, max_slots=1,
                            max_seq=16)
    eng = ServeEngine(model, params, pcfg)
    eng.scheduler.submit(Request(rid=0, prompt=np.zeros((8,), np.int32),
                                 max_new_tokens=4))
    with pytest.raises(OutOfPagesError, match="pool is idle yet too small"):
        eng.run()


def test_serve_engine_mode_validation(setup):
    cfg, model, params = setup
    pcfg = _pcfg()
    with pytest.raises(ValueError, match="unknown serve mode"):
        ServeEngine(model, params, pcfg, mode="speculative")
    with pytest.raises(ValueError, match="requires a mesh"):
        ServeEngine(model, params, pcfg, mode="explicit")
    assert SERVE_MODES == ("gspmd", "explicit")


# ---------------------------------------------------------------------------
# graceful degradation: preemption, deadlines, bounded retry
# ---------------------------------------------------------------------------


def test_bucket_clamps_to_max_context():
    from repro.serve.engine import _bucket
    assert _bucket(5) == 8
    assert _bucket(12, hi=16) == 16
    assert _bucket(12, hi=20) == 16   # pow2 still below the cap
    assert _bucket(17, hi=20) == 20   # top bucket is exactly the cap
    with pytest.raises(ValueError, match="max context"):
        _bucket(21, hi=20)


def test_serve_preemption_zero_lost_tokens(setup):
    """Under page exhaustion the engine evicts the youngest active, re-queues
    it with prompt+generated intact, and the resumed stream is token-exact
    vs a pool that never had to preempt."""
    cfg, model, params = setup
    rng = np.random.default_rng(7)
    pa = rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)

    big = ServeEngine(model, params,
                      PagedCacheConfig(page_size=4, num_pages=16,
                                       max_slots=2, max_seq=16))
    big.submit(pa, max_new_tokens=8)
    big.submit(pb, max_new_tokens=4)
    ref = big.run()

    # 4 pages: A (4+8 -> 3 pages) and B (4+4 -> 2 pages) cannot coexist
    small = ServeEngine(model, params,
                        PagedCacheConfig(page_size=4, num_pages=4,
                                         max_slots=2, max_seq=16),
                        preempt=True)
    small.submit(pa, max_new_tokens=8)
    small.submit(pb, max_new_tokens=4)
    out, stats = small.run(collect_stats=True)

    assert small.scheduler.preempted_total >= 1
    assert sum(s["preempted"] for s in stats) == small.scheduler.preempted_total
    for rid in ref:
        np.testing.assert_array_equal(ref[rid], out[rid])


def test_serve_preemption_bounded_per_request(setup):
    """No request is evicted past max_preemptions — the livelock guard."""
    cfg, model, params = setup
    rng = np.random.default_rng(8)
    eng = ServeEngine(model, params,
                      PagedCacheConfig(page_size=4, num_pages=4,
                                       max_slots=2, max_seq=16),
                      preempt=True)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, size=(4,))
                       .astype(np.int32), max_new_tokens=8)
            for _ in range(3)]
    out = eng.run()
    assert eng.scheduler.max_preemptions == 1
    assert set(out) == set(rids)
    for rid in rids:
        assert out[rid].shape[0] == 4 + 8  # nobody lost tokens


def test_serve_deadline_timeout_waiting_and_active(setup):
    import time as _time
    cfg, model, params = setup
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)

    # expires while waiting: pool busy is not even required — the deadline
    # check runs before admission
    eng = ServeEngine(model, params, _pcfg())
    eng.submit(prompt, max_new_tokens=4, deadline_s=1e-9)
    req = eng.scheduler.waiting[0]
    _time.sleep(0.01)
    stats = eng.step()
    assert req.done and req.finish_reason == "timeout"
    assert stats["timeouts"] == 1 and req.generated == []

    # expires mid-decode: partial generation is kept, slot recycles
    eng2 = ServeEngine(model, params,
                       PagedCacheConfig(page_size=4, num_pages=32,
                                        max_slots=3, max_seq=128))
    eng2.submit(prompt, max_new_tokens=64, deadline_s=0.05)
    req2 = eng2.scheduler.waiting[0]
    eng2.step()  # admit + prefill + first decode
    assert req2.slot is not None
    _time.sleep(0.06)
    eng2.step()
    assert req2.done and req2.finish_reason == "timeout"
    assert 0 < len(req2.generated) < 64
    assert req2.slot is None and eng2.alloc.free_slot_count == 3


def test_serve_bounded_retry_rejects_head(setup):
    """A head that cannot be admitted within admission_retries attempts is
    finished with reason 'rejected' instead of blocking forever."""
    cfg, model, params = setup
    rng = np.random.default_rng(10)
    pa = rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)
    pb = rng.integers(0, cfg.vocab_size, size=(4,)).astype(np.int32)
    eng = ServeEngine(model, params,
                      PagedCacheConfig(page_size=4, num_pages=8,
                                       max_slots=1, max_seq=16),
                      admission_retries=2)
    ra = eng.submit(pa, max_new_tokens=12)  # holds the only slot 12 steps
    rb = eng.submit(pb, max_new_tokens=4)
    reqb = eng.scheduler.waiting[1]  # [0] is A, admitted on the first step
    out, stats = eng.run(collect_stats=True)
    assert reqb.finish_reason == "rejected"
    assert sum(s["rejected"] for s in stats) == 1
    assert out[ra].shape[0] == 4 + 12    # the active stream was untouched
    np.testing.assert_array_equal(out[rb], pb)  # rejected: prompt only
