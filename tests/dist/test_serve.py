"""Engine-routed explicit tensor-parallel decode on the 8-device mesh.

The serving tentpole guarantee: ``make_decode_step_explicit`` — the paged
single-token decode inside one ``shard_map``, heads exchanged under
``decode.qkv``/``decode.out`` tags and the MoE dispatch/combine under
``decode.moe`` — must match the GSPMD ``make_paged_decode_step`` from
identical pages for EVERY registered ``all_to_all_tiles`` schedule: the
logits AND the page pool, at every decode step. The two programs share all
the math (the exchanges only relocate heads/capacity strips), so the
comparison is exact-tolerance f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.engine import schedules_for
from repro.launch.mesh import make_mesh
from repro.configs.qwen3_moe_235b_a22b import tiny
from repro.models import transformer as T
from repro.models.kvcache import (PagedCacheConfig, PageAllocator,
                                  commit_prefill)
from repro.models.model import build_model
from repro.train.serve import (make_decode_step_explicit,
                               make_paged_decode_step, make_prefill_step)

NDEV = 8
pytestmark = pytest.mark.skipif(
    len(jax.devices()) < NDEV, reason=f"needs {NDEV} devices")

B, S0, STEPS = NDEV, 5, 3
PAGE = 4


@pytest.fixture(scope="module")
def ring():
    return make_mesh((NDEV,), ("x",))


@pytest.fixture(scope="module")
def served():
    """Prefilled pages + the GSPMD decode trajectory (the reference)."""
    cfg = tiny(NDEV)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    pcfg = PagedCacheConfig(page_size=PAGE, max_slots=B, max_seq=S0 + STEPS,
                            num_pages=B * pcfg_pages(S0 + STEPS))
    prompts = jax.random.randint(jax.random.key(1), (B, S0), 0,
                                 cfg.vocab_size).astype(jnp.int32)

    prefill = make_prefill_step(model, None)
    alloc = PageAllocator(pcfg)
    pages = T.init_paged_cache(cfg, pcfg, jnp.float32)
    first = np.zeros((B, 1), np.int32)
    for b in range(B):
        slot = alloc.allocate(S0 + STEPS)
        c1 = model.init_cache(1, S0, jnp.float32)
        lg, c1 = prefill(params, {"tokens": prompts[b:b + 1]}, c1)
        pages["layers"] = commit_prefill(
            pages["layers"], c1["layers"],
            jnp.asarray(alloc.block_table[slot]), S0,
            page_size=pcfg.page_size)
        alloc.commit(slot, S0)
        first[slot, 0] = int(jnp.argmax(lg[0, -1]))

    # GSPMD reference trajectory: greedy tokens, logits and pages per step
    pd = make_paged_decode_step(model, None)
    ref = {"logits": [], "pages": [], "tables": [], "toks": [first]}
    pg = jax.tree.map(lambda a: a.copy(), pages)
    a2 = _clone_alloc(alloc, pcfg)
    tok = first
    for _ in range(STEPS):
        bt, ln = a2.device_tables()
        ref["tables"].append((bt, ln))
        lg, pg = pd(params, jnp.asarray(tok), pg, bt, ln)
        # np.array copies: np.asarray can alias the CPU device buffer,
        # which the donating decode step recycles on the next call
        ref["logits"].append(np.array(lg))
        ref["pages"].append([np.array(x) for x in jax.tree.leaves(pg)])
        for s in range(B):
            a2.append(s)
        tok = np.asarray(jnp.argmax(lg[:, -1], -1), np.int32)[:, None]
        ref["toks"].append(tok)
    return cfg, model, params, pcfg, alloc, pages, ref


def pcfg_pages(max_seq: int) -> int:
    return -(-max_seq // PAGE)


def _clone_alloc(alloc, pcfg):
    a2 = PageAllocator(pcfg)
    a2.block_table[:] = alloc.block_table
    a2.seq_lens[:] = alloc.seq_lens
    a2._capacity[:] = alloc._capacity
    return a2


@pytest.mark.parametrize(
    "schedule", [None] + sorted(schedules_for("all_to_all_tiles")))
def test_explicit_decode_matches_gspmd(served, ring, schedule):
    """Logits AND cache parity per decode step, per registered schedule
    (None = the cost-model "auto" resolution)."""
    cfg, model, params, pcfg, alloc, pages, ref = served
    pd_e = make_decode_step_explicit(model, ring, schedule=schedule)
    pe = jax.tree.map(lambda a: a.copy(), pages)
    for i in range(STEPS):
        bt, ln = ref["tables"][i]
        le, pe = pd_e(params, jnp.asarray(ref["toks"][i]), pe, bt, ln)
        np.testing.assert_allclose(np.asarray(le), ref["logits"][i],
                                   rtol=0, atol=2e-5)
        for got, want in zip(jax.tree.leaves(pe), ref["pages"][i]):
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=0, atol=2e-5)


def test_explicit_serve_engine_matches_gspmd_engine(served, ring):
    """End-to-end continuous batching: the explicit-mode ServeEngine must
    emit the same token streams as the GSPMD-mode engine on the same
    workload (mixed prompt lengths, slot churn)."""
    from repro.serve import ServeEngine
    cfg, model, params, _, _, _, _ = served

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in (5, 3, 7, 4, 6, 5, 4, 3, 6, 7)]
    max_new = 4

    def run(mode, mesh):
        pcfg = PagedCacheConfig(page_size=PAGE, max_slots=B, max_seq=16,
                                num_pages=B * pcfg_pages(16))
        eng = ServeEngine(model, params, pcfg, mode=mode, mesh=mesh,
                          prefill_token_budget=16)
        return eng.run(prompts, max_new_tokens=max_new, collect_stats=True)

    out_g, _ = run("gspmd", None)
    out_e, stats = run("explicit", ring)
    assert sum(1 for s in stats if s["prefills"] and s["decode_tokens"]) > 0
    for rid in out_g:
        np.testing.assert_array_equal(out_g[rid], out_e[rid])


def test_explicit_decode_divisibility_errors(ring):
    """Head counts that don't divide the axis must fail loudly at build."""
    from repro.configs import get_config, reduced
    cfg = reduced(get_config("llama3.2-3b"), layers=1, d_model=32)  # 4 heads
    model = build_model(cfg)
    with pytest.raises(ValueError, match="divisible"):
        make_decode_step_explicit(model, ring)


def test_explicit_engine_slot_divisibility(served, ring):
    from repro.serve import ServeEngine
    cfg, model, params, _, _, _, _ = served
    pcfg = PagedCacheConfig(page_size=PAGE, max_slots=NDEV + 1, max_seq=16,
                            num_pages=(NDEV + 1) * pcfg_pages(16))
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(model, params, pcfg, mode="explicit", mesh=ring)
