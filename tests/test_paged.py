"""Paged-KV serving subsystem: kernel, cache bookkeeping, scheduler, engine.

The load-bearing contracts:
* paged flash-decode ≡ contiguous flash-decode on the same logical KV
  (bit-exact: the block-table gather only changes *where* pages live);
* both ≡ the naive oracle under ragged lengths, GQA and sliding windows;
* the allocator/block-table invariants (trash page reserved, pages returned
  on release, admission is all-or-nothing);
* continuous batching preserves per-request generations exactly: packed
  prefill + paged decode through the engine reproduces one-request-at-a-time
  contiguous serving token for token;
* distributed paged serving (page-aligned pool shards, per-shard local
  attention + online-softmax partial merge) reproduces the single-device
  engine token for token — partial-state math in the fast tier, the real
  multi-device engine in a slow-tier subprocess with fake CPU devices;
* EOS finish: a sequence that emits its eos_id is evicted immediately (pages
  freed, decode steps saved), with the generation a prefix of the budget run;
* lazy admission (prompt-only reservation + one-page growth + youngest-row
  preemption/re-prefill) is token-identical to eager full-budget reservation
  under memory pressure that forces preemptions, at strictly higher pool
  utilization;
* sliding-window page reclamation frees only fully-out-of-window pages —
  poisoning every freed page (and the trash page) leaves generations
  bit-identical, so the kernels' window gate provably never reads them;
* recurrent-state slot lifecycle (StateCache) tracks page admission exactly:
  a slot's state row is bound on admit, released (and queued for poisoning)
  on release/preemption, and conserved under randomized churn —
  free + occupied == capacity at every step.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import max_err
from repro.core import online_softmax as osm
from repro.core.attention import spark_paged_decode, spark_paged_decode_partials
from repro.kernels.ops import (decode, gather_pages, paged_decode,
                               paged_decode_reference)
from repro.serving import (BlockTables, PageAllocator, PagedCacheConfig,
                           Request, Scheduler, StateCache, TRASH_PAGE)


def _mk_pool(key, b, hq, hkv, d, page_size, pages_per_row, extra_pages=3):
    """Random q + page pool + shuffled block tables for b rows."""
    num_pages = 1 + b * pages_per_row + extra_pages
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k_pages = jax.random.normal(ks[1], (hkv, num_pages, page_size, d))
    v_pages = jax.random.normal(ks[2], (hkv, num_pages, page_size, d))
    perm = np.random.RandomState(1).permutation(num_pages - 1) + 1
    bt = jnp.asarray(perm[:b * pages_per_row].reshape(b, pages_per_row),
                     jnp.int32)
    return q, k_pages, v_pages, bt


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

CASES = [
    # hq, hkv, page_size, window
    (4, 4, 64, None),      # MHA
    (8, 2, 64, None),      # GQA: group packed into MXU rows
    (4, 2, 64, 100),       # sliding window masked in-kernel (no ring)
    (4, 1, 128, None),     # MQA, bigger pages
]


@pytest.mark.parametrize("hq,hkv,ps,window", CASES,
                         ids=[str(c) for c in CASES])
def test_paged_kernel_matches_oracle(rng_key, hq, hkv, ps, window):
    b, d, t = 3, 64, 4
    q, kp, vp, bt = _mk_pool(rng_key, b, hq, hkv, d, ps, t)
    kv_len = jnp.array([t * ps, ps + 7, 3], jnp.int32)
    o = paged_decode(q, kp, vp, bt, kv_len, window=window, interpret=True)
    o_ref = paged_decode_reference(q, kp, vp, bt, kv_len, window=window)
    assert max_err(o, o_ref) < 2e-5


def test_paged_equals_contiguous_kernel(rng_key):
    """Same logical KV, scattered pages vs. contiguous layout: bit-exact."""
    b, hq, hkv, d, ps, t = 2, 8, 2, 64, 64, 4
    q, kp, vp, bt = _mk_pool(rng_key, b, hq, hkv, d, ps, t)
    kv_len = jnp.array([t * ps, 2 * ps - 5], jnp.int32)
    kc, vc = gather_pages(kp, bt), gather_pages(vp, bt)
    o_paged = paged_decode(q, kp, vp, bt, kv_len, interpret=True)
    o_contig = decode(q, kc, vc, kv_len=kv_len, block_kv=ps, interpret=True)
    assert max_err(o_paged, o_contig) == 0.0


def test_paged_trash_entries_are_inert(rng_key):
    """Entries past a row's allocation point at the trash page; whatever it
    holds must not leak into the output (the kv_len mask gates it)."""
    b, hq, hkv, d, ps, t = 2, 4, 2, 64, 64, 4
    q, kp, vp, bt = _mk_pool(rng_key, b, hq, hkv, d, ps, t)
    kv_len = jnp.array([ps + 3, 2 * ps], jnp.int32)
    bt_trashed = bt.at[:, 2:].set(TRASH_PAGE)     # rows only own 2 pages
    o1 = paged_decode(q, kp, vp, bt_trashed, kv_len, interpret=True)
    kp2 = kp.at[:, TRASH_PAGE].set(1e6)           # poison the trash page
    o2 = paged_decode(q, kp2, vp, bt_trashed, kv_len, interpret=True)
    assert max_err(o1, o2) == 0.0


def test_spark_paged_decode_xla_matches_kernel(rng_key):
    b, hq, hkv, d, ps, t = 2, 4, 2, 64, 64, 3
    q, kp, vp, bt = _mk_pool(rng_key, b, hq, hkv, d, ps, t)
    kv_len = jnp.array([t * ps, 70], jnp.int32)
    o_k = spark_paged_decode(q, kp, vp, bt, kv_len, impl="pallas_interpret")
    o_x = spark_paged_decode(q, kp, vp, bt, kv_len, impl="xla")
    assert max_err(o_k, o_x) < 2e-5


# ---------------------------------------------------------------------------
# distributed building block: per-shard partials + online-softmax merge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("window", [None, 20], ids=["full", "win20"])
def test_paged_partials_merge_equals_full(rng_key, impl, window):
    """Split the pool into two page-aligned 'shards' by hand: local partial
    attention per shard (non-local table entries remapped to the shard's
    trash page and masked via block_valid) merged with online_softmax.merge
    must reproduce the single-pool decode — the distributed serving math,
    exercised without any devices."""
    b, hq, hkv, d, ps, t = 3, 8, 2, 64, 16, 4
    num_pages, n_shards = 8, 2
    n_local = num_pages // n_shards
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (hkv, num_pages, ps, d))
    vp = jax.random.normal(ks[2], (hkv, num_pages, ps, d))
    # pages 0 and 4 are the per-shard trash pages; tables use the rest
    usable = np.array([1, 2, 3, 5, 6, 7])
    rs = np.random.RandomState(1)
    bt = jnp.asarray(np.stack([rs.permutation(usable)[:t] for _ in range(b)]
                              ).astype(np.int32))
    kv_len = jnp.array([t * ps, ps + 5, 3], jnp.int32)

    full = spark_paged_decode(q, kp, vp, bt, kv_len, impl=impl, window=window)
    states = []
    for s in range(n_shards):
        owner = bt // n_local
        valid = (owner == s).astype(jnp.int32)
        bt_local = jnp.where(owner == s, bt % n_local, 0)
        acc, m, l = spark_paged_decode_partials(
            q, kp[:, s * n_local:(s + 1) * n_local],
            vp[:, s * n_local:(s + 1) * n_local], bt_local, kv_len,
            block_valid=valid, impl=impl, window=window)
        states.append(osm.SoftmaxState(m=m, l=l, acc=acc))
    o, _ = osm.finalize(osm.merge(states[0], states[1]), out_dtype=q.dtype)
    assert max_err(o, full) < 2e-5


def test_paged_partials_trash_poison_inert(rng_key):
    """Poisoning a shard's trash page must not leak through block_valid."""
    b, hq, hkv, d, ps, t = 2, 4, 2, 32, 16, 2
    ks = jax.random.split(rng_key, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (hkv, 4, ps, d))
    vp = jax.random.normal(ks[2], (hkv, 4, ps, d))
    bt = jnp.array([[1, 2], [3, 1]], jnp.int32)
    kv_len = jnp.array([2 * ps, ps + 3], jnp.int32)
    valid = jnp.array([[1, 0], [0, 1]], jnp.int32)   # pretend half is foreign
    bt_masked = jnp.where(valid == 1, bt, 0)
    ref = spark_paged_decode_partials(q, kp, vp, bt_masked, kv_len,
                                      block_valid=valid, impl="xla")
    kp2 = kp.at[:, 0].set(1e6)                       # poison the trash page
    out = spark_paged_decode_partials(q, kp2, vp, bt_masked, kv_len,
                                      block_valid=valid, impl="xla")
    for a, b_ in zip(ref, out):
        assert max_err(a, b_) == 0.0


# ---------------------------------------------------------------------------
# cache bookkeeping
# ---------------------------------------------------------------------------

def test_page_allocator_invariants():
    a = PageAllocator(num_pages=6)               # pages 1..5 usable
    assert a.num_free == 5
    got = a.alloc(3)
    assert got is not None and TRASH_PAGE not in got
    assert a.alloc(3) is None                    # all-or-nothing: 2 left
    assert a.num_free == 2                       # failed alloc had no effect
    a.free(got)
    assert a.num_free == 5
    assert sorted(a.alloc(5)) == [1, 2, 3, 4, 5]


def test_page_allocator_per_shard_trash_pages():
    """Distributed pool: page 0 of every shard (global s·P) is reserved."""
    a = PageAllocator(num_pages=8, num_shards=2)     # trash: 0 and 4
    assert a.num_free == 6
    got = a.alloc(6)
    assert sorted(got) == [1, 2, 3, 5, 6, 7]
    with pytest.raises(ValueError):
        a.free([4])                                  # shard-1 trash page
    a.free(got)
    assert a.num_free == 6
    # config level: validation + derived geometry
    cfg = PagedCacheConfig(page_size=4, num_pages=8, max_batch=2,
                           max_pages_per_seq=4, num_shards=2)
    assert cfg.trash_pages == frozenset({0, 4}) and cfg.usable_pages == 6
    with pytest.raises(ValueError):                  # pages straddle shards
        PagedCacheConfig(page_size=4, num_pages=9, max_batch=2,
                         max_pages_per_seq=4, num_shards=2)


def test_block_tables_admit_release_utilization():
    cfg = PagedCacheConfig(page_size=4, num_pages=9, max_batch=2,
                           max_pages_per_seq=4)
    bt = BlockTables(cfg)
    assert bt.admit(0, n_tokens=10)              # 3 pages
    assert bt.admit(1, n_tokens=14)              # 4 pages
    assert bt.allocator.num_free == 1
    bt.kv_len[0], bt.kv_len[1] = 10, 14
    u = bt.utilization()
    assert u["used_tokens"] == 24 and u["allocated_tokens"] == 28
    bt.release(0)
    assert bt.allocator.num_free == 4
    assert np.all(bt.tables[0] == TRASH_PAGE) and bt.kv_len[0] == 0
    with pytest.raises(ValueError):
        bt.admit(0, n_tokens=cfg.max_seq_len + 1)


def test_block_tables_lazy_growth():
    """grow() allocates exactly the next write block, idempotently, and
    reports pool exhaustion without side effects."""
    cfg = PagedCacheConfig(page_size=4, num_pages=5, max_batch=2,
                           max_pages_per_seq=4)          # 4 usable pages
    bt = BlockTables(cfg)
    assert bt.admit(0, n_tokens=6)               # prompt-only: 2 pages
    bt.kv_len[0] = 6
    assert bt.append_dest_ok(0)                  # position 6 is in block 1
    assert bt.grow(0) and bt.pages_grown == 0    # idempotent: no allocation
    bt.kv_len[0] = 8                             # next write crosses a page
    assert not bt.append_dest_ok(0)
    assert bt.grow(0) and bt.pages_grown == 1
    assert bt.append_dest_ok(0)
    assert bt.tables[0, 2] != TRASH_PAGE
    assert bt.admit(1, n_tokens=4)               # 1 page → pool dry
    bt.kv_len[1] = 4
    free_before = bt.allocator.num_free
    assert not bt.grow(1)                        # dry: False, no side effect
    assert bt.allocator.num_free == free_before == 0
    bt.kv_len[0] = 11                            # last position of block 2
    assert bt.append_dest_ok(0)
    bt.kv_len[0] = 16                            # beyond the 4-block table
    with pytest.raises(ValueError):
        bt.grow(0)


def test_block_tables_window_reclaim():
    """reclaim_out_of_window frees exactly the blocks whose every position
    the decode kernels' window gate masks out — never an in-window page."""
    cfg = PagedCacheConfig(page_size=4, num_pages=10, max_batch=1,
                           max_pages_per_seq=6)
    bt = BlockTables(cfg)
    assert bt.admit(0, n_tokens=20)              # blocks 0..4
    bt.kv_len[0] = 20
    window = 6
    # next decode: q_pos = 20, keys allowed at positions > 14 → blocks 0..2
    # (last positions 3, 7, 11) are dead; block 3 (last position 15) lives
    freed = bt.reclaim_out_of_window(0, window)
    assert len(freed) == 3 and bt.pages_reclaimed == 3
    assert all(bt.tables[0, blk] == TRASH_PAGE for blk in range(3))
    assert all(bt.tables[0, blk] != TRASH_PAGE for blk in (3, 4))
    assert sorted(bt._owned[0]) == [3, 4]
    assert bt.reclaim_out_of_window(0, window) == []   # idempotent at this L
    u = bt.utilization()
    assert u["allocated_tokens"] == 8.0          # 2 owned pages
    assert u["used_tokens"] == 8.0               # tokens resident in them
    bt.kv_len[0] = 22                            # window slides with kv_len
    assert len(bt.reclaim_out_of_window(0, window)) == 1   # block 3 dies
    bt.release(0)
    assert bt.allocator.num_free == cfg.usable_pages
    # windowed admission skips the blocks reclaim would free immediately: a
    # resumed 20-token prompt reserves only its in-window tail (same horizon)
    sched = Scheduler(cfg, lazy=True, window=window)
    sched.submit(Request(rid=0, tokens=np.zeros(20, np.int32),
                         max_new_tokens=4))
    (seq,) = sched.admit()
    assert sorted(sched.tables._owned[seq.slot]) == [3, 4]
    assert all(sched.tables.tables[seq.slot, blk] == TRASH_PAGE
               for blk in range(3))


def test_scheduler_lazy_preempts_youngest_and_resumes():
    """Pool runs dry mid-growth → the youngest row is preempted: pages
    freed, request re-queued at the FRONT with generated tokens folded into
    the prompt and the budget shrunk; admission later resumes it."""
    cfg = PagedCacheConfig(page_size=4, num_pages=6, max_batch=2,
                           max_pages_per_seq=4)          # 5 usable pages
    sched = Scheduler(cfg, lazy=True)
    sched.submit(Request(rid=0, tokens=np.arange(8, dtype=np.int32),
                         max_new_tokens=8))
    sched.submit(Request(rid=1, tokens=np.arange(4, dtype=np.int32),
                         max_new_tokens=8))
    s0, s1 = sched.admit()                       # lazy: 2 + 1 pages
    assert sched.tables.allocator.num_free == 2
    s0.generated, s1.generated = [11], [21]
    sched.tables.kv_len[s0.slot], sched.tables.kv_len[s1.slot] = 8, 4
    assert sched.ensure_growth() == []           # 2 free pages cover both
    assert sched.tables.allocator.num_free == 0
    s0.generated += [12, 13, 14, 15]
    s1.generated += [22, 23, 24]
    sched.tables.kv_len[s0.slot], sched.tables.kv_len[s1.slot] = 12, 8
    preempted = sched.ensure_growth()            # dry → youngest (rid 1) out
    assert preempted == [1] and sched.preemptions == 1
    assert list(sched.active) == [s0.slot]
    assert sched.tables.append_dest_ok(s0.slot)  # the older row kept growing
    resumed = sched.waiting[0]                   # re-queued at the front
    assert resumed.rid == 1
    assert list(resumed.tokens) == list(np.arange(4)) + [21, 22, 23, 24]
    assert resumed.max_new_tokens == 4           # 8 - 4 already generated
    assert resumed.generated_prefix == [21, 22, 23, 24]
    assert resumed.budget_tokens == 12           # invariant under preemption
    # the survivor finishes → its pages cover the resumed prefix
    s0.generated += [16, 17, 18]                 # hits the budget of 8
    sched.evict_finished()
    (s1b,) = sched.admit()
    assert s1b.request.rid == 1 and s1b.request.generated_prefix == [21, 22,
                                                                     23, 24]


def test_prefill_dest_math():
    cfg = PagedCacheConfig(page_size=4, num_pages=9, max_batch=2,
                           max_pages_per_seq=4)
    bt = BlockTables(cfg)
    assert bt.admit(0, 6) and bt.admit(1, 5)     # 2 pages each
    seg = np.array([0, 0, 0, 0, 0, 0, 1, 1, 1, 1, 1, -1], np.int32)
    dest = bt.prefill_dest(seg, slots=[0, 1])
    t0, t1 = bt.tables[0], bt.tables[1]
    exp0 = [t0[0] * 4 + i for i in range(4)] + [t0[1] * 4, t0[1] * 4 + 1]
    exp1 = [t1[0] * 4 + i for i in range(4)] + [t1[1] * 4]
    assert list(dest[:6]) == exp0
    assert list(dest[6:11]) == exp1
    assert dest[11] < cfg.page_size              # padding → trash page slots


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_waves_and_fcfs():
    cfg = PagedCacheConfig(page_size=4, num_pages=5, max_batch=4,
                           max_pages_per_seq=4)
    sched = Scheduler(cfg)
    for rid in range(3):                         # each needs 2 pages; pool: 4
        sched.submit(Request(rid=rid, tokens=np.zeros(4, np.int32),
                             max_new_tokens=4))
    first = sched.admit()
    assert [s.request.rid for s in first] == [0, 1]   # FCFS, 2 fit
    assert sched.admit() == []                   # pool exhausted, order kept
    first[0].generated.extend([1] * 4)           # rid 0 finishes
    done = sched.evict_finished()
    assert [s.request.rid for s in done] == [0]
    second = sched.admit()                       # freed pages re-admit rid 2
    assert [s.request.rid for s in second] == [2]
    with pytest.raises(ValueError):              # can never fit → reject early
        sched.submit(Request(rid=9, tokens=np.zeros(14, np.int32),
                             max_new_tokens=4))


def test_eos_finishes_sequence_early():
    """ActiveSeq.done fires on the EOS token, not just the budget."""
    from repro.serving import ActiveSeq
    req = Request(rid=0, tokens=np.zeros(4, np.int32), max_new_tokens=8,
                  eos_id=7)
    seq = ActiveSeq(request=req, slot=0)
    seq.generated.extend([3, 5])
    assert not seq.done
    seq.generated.append(7)                      # EOS
    assert seq.done
    # without an eos_id the same tokens run to the budget
    req2 = Request(rid=1, tokens=np.zeros(4, np.int32), max_new_tokens=8)
    seq2 = ActiveSeq(request=req2, slot=1)
    seq2.generated.extend([3, 5, 7])
    assert not seq2.done
    seq2.generated.extend([7] * 5)
    assert seq2.done                             # budget


# ---------------------------------------------------------------------------
# end to end: packed prefill + paged decode ≡ contiguous serving
# ---------------------------------------------------------------------------

def _smoke_cfg():
    from repro import configs
    return dataclasses.replace(configs.smoke_config("qwen3_14b"),
                               dtype=jnp.float32, remat=False)


def test_engine_matches_contiguous_serving():
    from repro.models import lm
    from repro.runtime.steps import make_serve_steps
    from repro.serving import ServingEngine

    cfg = _smoke_cfg()
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    # two prompt lengths only (bounds baseline recompiles); ragged budgets
    reqs = [(rs.randint(0, cfg.vocab_size, size=L).astype(np.int32), g)
            for L, g in [(12, 6), (7, 8), (12, 1), (7, 5)]]

    def contiguous_gen(prompt, max_new, max_len=24):
        arts = make_serve_steps(cfg, impl="xla", max_len=max_len, batch=1,
                                xla_chunk=16)
        caches = arts.cache_init_fn()
        logits, caches = arts.prefill_fn(params, jnp.asarray(prompt)[None],
                                         None, caches)
        tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
        out = [int(tok[0])]
        for i in range(max_new - 1):
            logits, caches = arts.decode_fn(params, tok, caches,
                                            jnp.int32(len(prompt) + i))
            tok = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)
            out.append(int(tok[0]))
        return np.asarray(out, np.int32)

    expected = {i: contiguous_gen(p, g) for i, (p, g) in enumerate(reqs)}

    # pool sized so only ~2 sequences fit at once → real admission waves
    pcfg = PagedCacheConfig(page_size=8, num_pages=8, max_batch=2,
                            max_pages_per_seq=3)
    eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                        xla_chunk=16)
    out, stats = eng.run(reqs)
    assert stats["mean_utilization"] > 0.5       # pages track live tokens
    for rid, exp in expected.items():
        assert np.array_equal(out[rid], exp), \
            f"request {rid}: paged {out[rid]} != contiguous {exp}"
    # every page returned to the pool after the queue drained
    assert eng.scheduler.tables.allocator.num_free == pcfg.num_pages - 1


def test_packed_prefill_matches_per_prompt_prefill():
    """One packed prefill row fills two prompts' pages identically to two
    separate (unpacked) prefills — same last-token logits, same page bytes."""
    from repro.models import lm
    from repro.runtime.steps import make_serve_steps

    cfg = _smoke_cfg()
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(3)
    lens = [9, 6]
    prompts = [rs.randint(0, cfg.vocab_size, size=L).astype(np.int32)
               for L in lens]
    pcfg = PagedCacheConfig(page_size=4, num_pages=9, max_batch=2,
                            max_pages_per_seq=3)
    arts = make_serve_steps(cfg, impl="xla", paged=pcfg, xla_chunk=16)

    def run_prefill(layouts):
        """layouts: list of (prompt, slot) packed into one row per call."""
        tables = BlockTables(pcfg)
        caches = arts.cache_init_fn()
        last = {}
        for group in layouts:
            S = 16
            tokens = np.zeros((1, S), np.int32)
            seg = np.full((1, S), -1, np.int32)
            pos = np.zeros((1, S), np.int32)
            slots = np.full((1, S), -1, np.int32)
            off = 0
            for i, (prompt, slot) in enumerate(group):
                if slot not in tables._owned:
                    assert tables.admit(slot, len(prompt))
                n = len(prompt)
                tokens[0, off:off + n] = prompt
                seg[0, off:off + n] = i
                pos[0, off:off + n] = np.arange(n)
                slots[0, off:off + n] = slot
                off += n
            dest = tables.prefill_dest(seg[0], [s for _, s in group])
            logits, caches = arts.prefill_fn(
                params, jnp.asarray(tokens), jnp.asarray(seg),
                jnp.asarray(pos), jnp.asarray(dest[None]),
                jnp.asarray(slots), caches)
            off = 0
            for i, (prompt, slot) in enumerate(group):
                off += len(prompt)
                last[slot] = np.asarray(logits[0, off - 1, :cfg.vocab_size])
        return last, caches

    packed, caches_p = run_prefill([[(prompts[0], 0), (prompts[1], 1)]])
    solo, caches_s = run_prefill([[(prompts[0], 0)], [(prompts[1], 1)]])
    for slot in (0, 1):
        assert max_err(packed[slot], solo[slot]) < 1e-5
    # the cache pages must match too (page allocation order is deterministic,
    # so the layouts agree page for page). Page 0 is excluded: it is the
    # trash page and absorbs each layout's different padding writes.
    for lp, ls in zip(jax.tree.leaves(caches_p), jax.tree.leaves(caches_s)):
        assert max_err(lp[..., 1:, :, :], ls[..., 1:, :, :]) < 1e-5


def test_lazy_engine_matches_eager_under_preemption():
    """The acceptance contract of scheduler v2: with a pool tight enough to
    force at least one preemption, the lazy engine (prompt-only admission +
    growth + preempt/re-prefill) generates exactly the eager full-budget
    engine's tokens, at strictly higher reserved-vs-live page utilization."""
    from repro.models import lm
    from repro.serving import ServingEngine

    cfg = _smoke_cfg()
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    reqs = [(rs.randint(0, cfg.vocab_size, size=9).astype(np.int32), 6),
            (rs.randint(0, cfg.vocab_size, size=5).astype(np.int32), 8)]
    # 6 usable pages: eager serves the two 4-page-budget requests serially;
    # lazy admits both at once (3 + 2 prompt pages) and runs dry growing
    pcfg = PagedCacheConfig(page_size=4, num_pages=7, max_batch=2,
                            max_pages_per_seq=4)

    def run(lazy):
        eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=16,
                            xla_chunk=16, lazy=lazy)
        out, stats = eng.run(list(reqs))
        # every page back in the pool once the queue drains
        assert eng.scheduler.tables.allocator.num_free == pcfg.usable_pages
        return out, stats

    out_e, st_e = run(lazy=False)
    out_l, st_l = run(lazy=True)
    assert st_e["preemptions"] == 0 and st_e["pages_grown"] == 0
    assert st_l["preemptions"] >= 1          # the pressure actually bit
    assert set(out_e) == set(out_l)
    for rid in out_e:
        assert np.array_equal(out_l[rid], out_e[rid]), \
            f"request {rid}: lazy {out_l[rid]} != eager {out_e[rid]}"
    assert st_l["mean_utilization"] > st_e["mean_utilization"]


def test_window_reclamation_poisoned_pages_inert():
    """Sliding-window serving frees pages that slid fully out of the window.
    Poisoning every freed page (and the trash page their table entries now
    alias) with 1e6 must leave the generation bit-identical to a run that
    never reclaims — i.e. reclamation never frees an in-window page and the
    kernels' window gate never reads a reclaimed one."""
    from repro.models import lm
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(_smoke_cfg(), attn_window=10)
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(1)
    reqs = [(rs.randint(0, cfg.vocab_size, size=8).astype(np.int32), 12),
            (rs.randint(0, cfg.vocab_size, size=11).astype(np.int32), 9)]
    # 5 usable pages vs a ~3-page window footprint per row: tight enough
    # that lazy growth preempts, so the preempt/re-prefill path runs
    # *combined* with reclamation (a resumed long-tail prompt re-admits
    # with only its in-window blocks reserved)
    pcfg = PagedCacheConfig(page_size=4, num_pages=6, max_batch=2,
                            max_pages_per_seq=6)

    def run(**kw):
        eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                            xla_chunk=16, lazy=True, **kw)
        out, stats = eng.run(list(reqs))
        assert eng.scheduler.tables.allocator.num_free == pcfg.usable_pages
        return out, stats

    out_ref, st_ref = run(reclaim=False)
    out_rec, st_rec = run(poison_reclaimed=True)
    assert st_ref["pages_reclaimed"] == 0
    assert st_rec["pages_reclaimed"] > 0     # long tails actually reclaimed
    assert st_rec["preemptions"] >= 1        # ...while preemption also bites
    for rid in out_ref:
        assert np.array_equal(out_rec[rid], out_ref[rid]), \
            f"request {rid}: reclaimed {out_rec[rid]} != pinned {out_ref[rid]}"
    # reclamation holds O(window) pages per long row instead of O(seq):
    # the pool footprint must shrink (the utilization *fraction* may not —
    # a window straddling two partially-dead pages is sparser per page)
    assert st_rec["mean_pool_fraction"] < st_ref["mean_pool_fraction"]


def test_engine_eos_early_finish():
    """EOS eviction: generation is a prefix of the budget run, the decode
    loop stops spending steps on the finished sequence, and its pages return
    to the pool."""
    from repro.models import lm
    from repro.serving import ServingEngine

    cfg = _smoke_cfg()
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, cfg.vocab_size, size=12).astype(np.int32)
    pcfg = PagedCacheConfig(page_size=8, num_pages=8, max_batch=2,
                            max_pages_per_seq=3)

    def run(eos_id):
        eng = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                            xla_chunk=16)
        eng.submit(prompt, 8, eos_id=eos_id)
        out, stats = eng.run()
        assert eng.scheduler.tables.allocator.num_free == pcfg.usable_pages
        return out[0], stats

    ref, ref_stats = run(None)                       # runs to the budget
    assert len(ref) == 8
    eos = int(ref[2])                                # make step 3 the EOS
    got, got_stats = run(eos)
    assert list(got) == list(ref[:3])                # prefix, ends at EOS
    assert got_stats["decode_steps"] < ref_stats["decode_steps"]


# ---------------------------------------------------------------------------
# distributed: sharded engine ≡ single-device engine (fake CPU devices)
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_sharded_engine_matches_single_device():
    """Paged serving on a 2-way ("model",) mesh — page pool sharded
    page-aligned, decode via per-shard partials + online-softmax merge —
    reproduces the single-device engine token for token, in both admission
    modes. The lazy run uses a pool tight enough to force a preemption, so
    growth/preempt/re-prefill exercise the sharded decode path too (block
    tables keep global ids: every shard sees the same post-growth tables
    each step). Subprocess: the fake-device XLA flag must be set before jax
    initialises."""
    code = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving import PagedCacheConfig, ServingEngine

cfg = dataclasses.replace(configs.smoke_config("qwen3_14b"),
                          dtype=jnp.float32, remat=False)
params, _ = lm.init_params(cfg, jax.random.PRNGKey(0), vocab_pad_to=2)
rs = np.random.RandomState(0)
reqs = [(rs.randint(0, cfg.vocab_size, size=L).astype(np.int32), g)
        for L, g in [(12, 6), (7, 8), (12, 1), (7, 5)]]

pcfg = PagedCacheConfig(page_size=8, num_pages=8, max_batch=2,
                        max_pages_per_seq=3)
eng1 = ServingEngine(cfg, pcfg, params, impl="xla", prefill_len=24,
                     xla_chunk=16)
out1, _ = eng1.run(list(reqs))

mesh = make_mesh((2,), ("model",))
pcfg2 = dataclasses.replace(pcfg, num_shards=2)
eng2 = ServingEngine(cfg, pcfg2, params, impl="xla", prefill_len=24,
                     xla_chunk=16, mesh=mesh)
out2, stats2 = eng2.run(list(reqs))

assert set(out1) == set(out2)
for rid in out1:
    assert np.array_equal(out1[rid], out2[rid]), \\
        f"request {rid}: sharded {out2[rid]} != single-device {out1[rid]}"
assert eng2.scheduler.tables.allocator.num_free == pcfg2.usable_pages

# lazy + sharded: 6-page pool → 4 usable across 2 shards; growth runs the
# pool dry and preempts, all against the sharded decode/prefill steps
pcfg3 = PagedCacheConfig(page_size=8, num_pages=6, max_batch=2,
                         max_pages_per_seq=3, num_shards=2)
eng3 = ServingEngine(cfg, pcfg3, params, impl="xla", prefill_len=24,
                     xla_chunk=16, mesh=mesh, lazy=True)
out3, stats3 = eng3.run(list(reqs))
assert stats3["preemptions"] >= 1, stats3
assert stats3["pages_grown"] >= 1, stats3
for rid in out1:
    assert np.array_equal(out1[rid], out3[rid]), \\
        f"request {rid}: sharded-lazy {out3[rid]} != eager {out1[rid]}"
assert eng3.scheduler.tables.allocator.num_free == pcfg3.usable_pages
print("PASS")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True, timeout=480)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    assert "PASS" in out.stdout


# ---------------------------------------------------------------------------
# recurrent-state slot cache (StateCache)
# ---------------------------------------------------------------------------

def test_state_cache_tracks_preemption_lifecycle():
    """Mirror of test_scheduler_lazy_preempts_youngest_and_resumes at the
    state layer: admission binds a slot's recurrent-state row, preemption
    releases it (queued for poisoning), and the resumed request re-admits
    a freshly-poisoned row — occupancy tracks the scheduler exactly."""
    cfg = PagedCacheConfig(page_size=4, num_pages=6, max_batch=2,
                           max_pages_per_seq=4)
    sched = Scheduler(cfg, lazy=True)
    state = sched.tables.state
    assert state.num_free == 2 and state.num_occupied == 0
    sched.submit(Request(rid=0, tokens=np.arange(8, dtype=np.int32),
                         max_new_tokens=8))
    sched.submit(Request(rid=1, tokens=np.arange(4, dtype=np.int32),
                         max_new_tokens=8))
    s0, s1 = sched.admit()
    assert state.num_occupied == 2 and state.num_free == 0
    assert state.occupied(s0.slot) and state.occupied(s1.slot)
    assert state.drain_released() == []          # nothing released yet
    s0.generated, s1.generated = [11], [21]
    sched.tables.kv_len[s0.slot], sched.tables.kv_len[s1.slot] = 8, 4
    sched.ensure_growth()
    s0.generated += [12, 13, 14, 15]
    s1.generated += [22, 23, 24]
    sched.tables.kv_len[s0.slot], sched.tables.kv_len[s1.slot] = 12, 8
    preempted_slot = s1.slot
    assert sched.ensure_growth() == [1]          # youngest (rid 1) preempted
    # the preempted row's state died with its pages
    assert not state.occupied(preempted_slot)
    assert state.num_occupied == 1 and state.num_free == 1
    assert state.drain_released() == [preempted_slot]
    assert state.drain_released() == []          # drain-once semantics
    # survivor finishes → its row is released too; the resumed request then
    # re-admits into a clean row
    s0.generated += [16, 17, 18]
    sched.evict_finished()
    assert state.num_occupied == 0
    assert state.drain_released() == [s0.slot]
    (s1b,) = sched.admit()
    assert state.occupied(s1b.slot) and state.num_occupied == 1
    assert state.admits == 3 and state.releases == 2


def test_state_cache_guards():
    c = StateCache(2)
    c.admit(0)
    with pytest.raises(ValueError):
        c.admit(0)                               # double admit
    with pytest.raises(ValueError):
        c.admit(2)                               # out of range
    with pytest.raises(ValueError):
        c.release(1)                             # never admitted
    c.release(0)
    with pytest.raises(ValueError):
        c.release(0)                             # double release


def test_state_cache_randomized_conservation():
    """Random admit/release churn via the scheduler keeps state slots
    conserved (free + occupied == capacity, the sets disjoint) and in
    lock-step with page-table slot ownership; every released slot shows
    up in the poison queue exactly once."""
    cfg = PagedCacheConfig(page_size=4, num_pages=12, max_batch=3,
                           max_pages_per_seq=5)
    rs = np.random.RandomState(7)
    sched = Scheduler(cfg, lazy=True)
    state = sched.tables.state
    next_rid = 0
    drained = []

    def check():
        assert state.num_free + state.num_occupied == cfg.max_batch
        occ = {s for s in range(cfg.max_batch) if state.occupied(s)}
        assert len(occ) == state.num_occupied
        assert occ == set(sched.tables._owned)   # lock-step with the pages

    for step in range(300):
        op = rs.randint(5)
        if op == 0 and len(sched.waiting) < 4:
            sched.submit(Request(
                rid=next_rid,
                tokens=rs.randint(0, 5, size=int(rs.randint(2, 10))
                                  ).astype(np.int32),
                max_new_tokens=int(rs.randint(1, 6))))
            next_rid += 1
        elif op == 1:
            for seq in sched.admit():
                seq.prefilled = seq.request.prompt_len
                sched.tables.kv_len[seq.slot] = seq.request.prompt_len
                seq.generated.append(int(rs.randint(5)))
        elif op == 2 and sched.active:
            sched.ensure_growth()
            for seq in list(sched.active.values()):
                if not seq.done and sched.tables.append_dest_ok(seq.slot):
                    sched.tables.kv_len[seq.slot] += 1
                    seq.generated.append(int(rs.randint(5)))
        elif op == 3:
            sched.evict_finished()
        elif op == 4:
            drained.extend(state.drain_released())
        check()
    sched.evict_finished()
    drained.extend(state.drain_released())
    # every release was queued for poisoning exactly once
    assert len(drained) == state.releases
    assert state.admits - state.releases == state.num_occupied


@pytest.mark.parametrize("cache", ["packed_paged", "contiguous"])
def test_prefill_pallas_matches_xla(cache):
    """The served prefills through the Pallas forward kernel with its
    shape-chosen tiles (1,200 tokens: two 640-token blocks) match the XLA
    path: a packed row of three prompts and padding onto the paged pool
    (the engine's layout), and one prompt into a contiguous cache."""
    from repro.models import lm
    from repro.runtime.steps import make_serve_steps

    cfg = _smoke_cfg()
    params, _ = lm.init_params(cfg, jax.random.PRNGKey(0))
    S, lens = 1200, [500, 300, 150]
    tokens = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=(1, S)).astype(np.int32)
    out = {}
    for impl in ("xla", "pallas_interpret"):
        if cache == "contiguous":
            arts = make_serve_steps(cfg, impl=impl, max_len=S, batch=1,
                                    xla_chunk=256)
            logits, _ = arts.prefill_fn(params, jnp.asarray(tokens), None,
                                        arts.cache_init_fn())
            out[impl] = np.asarray(logits)
            continue
        pcfg = PagedCacheConfig(page_size=16, num_pages=64, max_batch=3,
                                max_pages_per_seq=32)
        arts = make_serve_steps(cfg, impl=impl, paged=pcfg, xla_chunk=256)
        tables = BlockTables(pcfg)
        seg = np.full((1, S), -1, np.int32)
        pos = np.zeros((1, S), np.int32)
        slots = np.full((1, S), -1, np.int32)
        off = 0
        for slot, n in enumerate(lens):
            assert tables.admit(slot, n)
            seg[0, off:off + n], slots[0, off:off + n] = slot, slot
            pos[0, off:off + n] = np.arange(n)
            off += n
        dest = tables.prefill_dest(seg[0], list(range(len(lens))))
        logits, _ = arts.prefill_fn(
            params, jnp.asarray(tokens), jnp.asarray(seg), jnp.asarray(pos),
            jnp.asarray(dest[None]), jnp.asarray(slots), arts.cache_init_fn())
        out[impl] = np.asarray(logits)[:, :off]
    assert max_err(out["pallas_interpret"], out["xla"]) < 1e-4
