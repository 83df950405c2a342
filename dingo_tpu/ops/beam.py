"""Batched lockstep beam search over a device-resident graph index.

TPU-native HNSW serving (ROADMAP item 5 / ISSUE 8 tentpole): a pointer
graph walks one query at a time; this kernel walks hundreds of queries
in lockstep over the FLATTENED level-0
adjacency — a dense ``[capacity, deg]`` int32 array in slot space
(SlotStore.adj) — so every step is regular gather + matmul + masked
top-k work the MXU/VPU are built for:

  frontier gather    each beam entry is expanded ONCE: a round takes the
                     best ``frontier_width(beam)`` entries not expanded
                     yet (an expanded entry's neighbours are all visited
                     already, so expanding it again gathers rows for
                     nothing) and one ``jnp.take`` on the adjacency turns
                     [b, width] slots into [b, width*deg] candidate slots
  candidate scores   the survivors of the visited mask and the dedup are
                     sorted to the front and the wave is cut in half
                     (``CAND_SHARE``); one ``[b, width*deg/2] x d`` einsum
                     against the SlotStore rows (bf16 pairs down for the
                     bf16 tier, sq8 decodes on the fly — the PR 4
                     precision tiers)
  visited set        a per-query PACKED bitmask over capacity
                     ([b, capacity/32] uint32, 1 bit per slot), seeded
                     with the store-invalid slots so that one lookup
                     gates "seen" and "deleted" alike. Marking
                     uses scatter-ADD, which is a correct bitwise OR
                     here: a slot passes the not-yet-visited mask at
                     most once over the whole walk and in-batch
                     duplicates are removed first, so no bit is ever
                     added twice
  dedup              candidates sort by slot id per iteration; repeats
                     (two beam entries sharing an unvisited neighbor)
                     mask to -1 so they cannot burn beam width
  beam update        masked ``lax.top_k`` over old beam + candidates

Termination: a fixed iteration cap (``hnsw.max_iters``) plus an
early-exit-by-convergence flag — a query goes inactive once every live
entry of its beam has been expanded (a round that admits no new
candidate leaves none to expand), and the ``lax.while_loop`` stops when
every query is inactive. Inactive queries ride along (lockstep has no
partial shapes) but cannot change state: their frontier is empty.

Filter pushdown (the PR 3 filter-mask cache, applied device-side): the
kernel keeps TWO candidate lists. The ROUTING beam admits any
store-valid node — a filtered-out node must still conduct the walk or
low-selectivity filters would disconnect the graph — while the RESULT
list only ever admits mask-eligible candidates, so masked candidates
never enter the beam the caller reranks and no host post-filter pass
exists. Unfiltered searches pass the validity mask for both and the two
lists coincide.

Returned slots are UNORDERED evidence: the caller reranks them with the
exact device rerank (ops/rerank.py), which sets the final ordering.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from dingo_tpu.obs.sentinel import sentinel_jit

#: beam entries expanded per round (the CAGRA "search width"): bounds the
#: round's candidate wave at FRONTIER * deg slots whatever the beam. On the
#: v5e a round costs by the slot (the visited-bit lookup is a scalar
#: gather, ~12 ns each), not by the byte: 32 reads fewer slots in all
#: than 64 or 128 at the same recall (PERF.md, PR 31)
FRONTIER = 32

#: candidate slots a round scores, as a share of the wave: after the
#: visited mask and the dedup most slots are holes (a tenth are new), so
#: the survivors are sorted to the front and the wave is cut in half
#: before its rows are gathered. A survivor past the cut is dropped (its
#: parent is not expanded again): it needs over half a wave of NEW
#: distinct rows, which only a walk's first rounds can come near
CAND_SHARE = 2


def frontier_width(beam: int) -> int:
    """Beam entries one round expands (static, from the beam bucket)."""
    return max(1, min(int(beam), FRONTIER))


def round_slots(beam: int, deg: int) -> int:
    """Candidate slots one round reads the adjacency for (what
    ``hnsw.gathered_rows_per_query`` counts a round as)."""
    return frontier_width(beam) * int(deg)


def _candidate_scores(vecs, sqnorm, qd, slots, metric, sq, vmin, scale):
    """'Larger is better' scores [b, C] for candidate slots [b, C] (-1 =
    hole, scored -inf). One gather + one einsum through the SAME metric
    math as the rerank kernels (ops/rerank._scores_from_rows) — the
    byte-identical host/device ordering guarantee depends on it; bf16
    tiers pair the query down, sq8 decodes to the bf16 surrogate, f32
    accumulation everywhere."""
    from dingo_tpu.ops.rerank import _scores_from_rows

    safe = jnp.where(slots >= 0, slots, 0)
    rows = jnp.take(vecs, safe, axis=0)                  # [b, C, d]
    if sq:
        from dingo_tpu.ops.sq import sq_decode_device

        rows = sq_decode_device(rows, vmin, scale)       # bf16 surrogate
    csq = jnp.take(sqnorm, safe)
    scores = _scores_from_rows(rows, csq, qd, metric)
    return jnp.where(slots >= 0, scores, -jnp.inf)


@sentinel_jit("ops.beam.search",
              static_argnames=("beam", "max_iters", "metric", "sq"))
def beam_search(adj, vecs, sqnorm, valid, fmask, queries, entry, vmin,
                scale, beam, max_iters, metric, sq):
    """Lockstep graph walk; see module docstring for the design.

    adj     [cap, deg] int32 slot-space adjacency (-1 padded)
    vecs    [cap, d] rows (f32 / bf16 / uint8 sq codes when sq=True)
    sqnorm  [cap] f32 stored/decoded row norms (SlotStore convention)
    valid   [cap] bool — store validity: gates ROUTING and results
    fmask   [cap] bool — filter pushdown: gates RESULTS only (pass
            `valid` again when unfiltered)
    queries [b, d] f32 (pre-normalized for cosine), entry [] int32
            slot of the graph entry point (-1 = empty graph)
    vmin/scale [d] f32 sq8 codec params (ignored when sq=False)

    Returns (res_slots [b, beam] int32 candidate set (-1 padded,
    unordered — rerank it), hops [b] int32 expansion rounds per query,
    visited [b] int32 marked-slot count, occupancy [b] int32 live
    result entries).
    """
    b, _ = queries.shape
    cap, deg = adj.shape
    nwords = (cap + 31) // 32
    qd = queries.astype(jnp.float32)
    res_ok = valid & fmask
    rowix = jnp.arange(b)[:, None]

    def score(slots):
        return _candidate_scores(
            vecs, sqnorm, qd, slots, metric, sq, vmin, scale
        )

    entry = entry.astype(jnp.int32)
    entry_ok = entry >= 0
    e_safe = jnp.maximum(entry, 0)
    # store-invalid slots start out "visited": ONE bit lookup a slot then
    # gates both (a second scalar gather, of `valid`, cost a round as much
    # as the lookup itself)
    inv = jnp.pad(~valid, (0, nwords * 32 - cap)).reshape(nwords, 32)
    inv_words = jnp.sum(
        inv.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)[None, :],
        axis=1, dtype=jnp.uint32,
    )
    ebit = jnp.where(
        entry_ok,
        jnp.uint32(1) << (e_safe.astype(jnp.uint32) & 31),
        jnp.uint32(0),
    )
    visited = jnp.broadcast_to(
        inv_words | jnp.where(jnp.arange(nwords) == (e_safe >> 5), ebit, 0),
        (b, nwords),
    )

    # seed: the entry always anchors the ROUTING beam (even when it is
    # tombstoned or filtered out — its neighbors must still be reachable;
    # a -inf score drops it at the first merge, after expansion), and
    # joins the RESULT list only when eligible.
    bslots = jnp.full((b, beam), -1, jnp.int32).at[:, 0].set(
        jnp.where(entry_ok, entry, -1)
    )
    es = score(jnp.broadcast_to(entry, (b, 1)))[:, 0]
    e_elig = entry_ok & jnp.take(res_ok, e_safe)
    bscores = jnp.full((b, beam), -jnp.inf, jnp.float32).at[:, 0].set(
        jnp.where(entry_ok & jnp.take(valid, e_safe), es, -jnp.inf)
    )
    rslots = jnp.full((b, beam), -1, jnp.int32).at[:, 0].set(
        jnp.where(e_elig, entry, -1)
    )
    rscores = jnp.full((b, beam), -jnp.inf, jnp.float32).at[:, 0].set(
        jnp.where(e_elig, es, -jnp.inf)
    )
    active = jnp.broadcast_to(entry_ok, (b,))
    hops = jnp.zeros((b,), jnp.int32)
    width = frontier_width(beam)
    keep = max(1, width * deg // CAND_SHARE)
    bexp = jnp.zeros((b, beam), bool)
    floor = jnp.finfo(jnp.float32).min

    def cond(st):
        it, active = st[0], st[7]
        return (it < max_iters) & jnp.any(active)

    def body(st):
        (it, bslots, bscores, bexp, rslots, rscores, visited, active,
         hops) = st
        hops = hops + active.astype(jnp.int32)
        # 1) frontier gather: the best `width` unexpanded beam entries
        #    expand one hop (the floor keeps a tombstoned entry seed,
        #    scored -inf, expandable: its neighbours must stay reachable)
        fkey = jnp.where(
            bexp | (bslots < 0), -jnp.inf, jnp.maximum(bscores, floor)
        )
        fv, fi = lax.top_k(fkey, width)
        fslots = jnp.where(
            jnp.isneginf(fv), -1, jnp.take_along_axis(bslots, fi, axis=1)
        )
        # top_k's -inf picks are holes or expanded already: marking them
        # changes nothing
        bexp = bexp.at[rowix, fi].set(True)
        neigh = jnp.take(adj, jnp.maximum(fslots, 0), axis=0)
        neigh = jnp.where((fslots >= 0)[:, :, None], neigh, -1)
        neigh = neigh.reshape(b, width * deg)            # [b, width*deg]
        # 2) drop holes, already-visited and store-invalid candidates (the
        #    invalid ones were marked visited before the walk)
        ok = neigh >= 0
        safe_n = jnp.where(ok, neigh, 0)
        words = safe_n >> 5
        bits = (safe_n & 31).astype(jnp.uint32)
        seen = (jnp.take_along_axis(visited, words, axis=1) >> bits) & 1
        new = ok & (seen == 0)
        # 3) in-batch dedup: sort by slot (cap sorts holes last), mask
        #    runs — duplicates of one slot carry identical scores, so
        #    keeping the first survivor is exact
        cs = jnp.where(new, safe_n, cap).astype(jnp.int32)
        cs = jnp.sort(cs, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros((b, 1), bool), cs[:, 1:] == cs[:, :-1]], axis=1
        )
        # survivors first (a second sort), then the wave is cut: the row
        # gather, the einsum and both merges run on `keep` slots
        cs = jnp.sort(jnp.where(dup, cap, cs), axis=1)[:, :keep]
        cand = jnp.where(cs < cap, cs, -1)
        # 4) one einsum scores the whole candidate wave
        cscores = score(cand)
        # 5) mark survivors visited (scatter-add == OR: each slot
        #    survives the not-visited mask at most once per walk, and
        #    step 3 removed in-batch repeats)
        csafe = jnp.where(cand >= 0, cand, 0)
        addv = jnp.where(
            cand >= 0,
            jnp.uint32(1) << (csafe.astype(jnp.uint32) & 31),
            jnp.uint32(0),
        )
        visited = visited.at[rowix, csafe >> 5].add(addv)
        # 6) routing-beam merge: any store-valid candidate competes
        mv, mi = lax.top_k(
            jnp.concatenate([bscores, cscores], axis=1), beam
        )
        mslots = jnp.take_along_axis(
            jnp.concatenate([bslots, cand], axis=1), mi, axis=1
        )
        mslots = jnp.where(jnp.isneginf(mv), -1, mslots)
        mexp = jnp.take_along_axis(
            jnp.concatenate([bexp, jnp.zeros(cand.shape, bool)], axis=1),
            mi, axis=1,
        )
        # 7) result merge: masked candidates never enter this beam
        relig = (cand >= 0) & jnp.take(res_ok, csafe)
        rv, ri = lax.top_k(
            jnp.concatenate(
                [rscores, jnp.where(relig, cscores, -jnp.inf)], axis=1
            ),
            beam,
        )
        nrslots = jnp.take_along_axis(
            jnp.concatenate([rslots, cand], axis=1), ri, axis=1
        )
        nrslots = jnp.where(jnp.isneginf(rv), -1, nrslots)
        # 8) convergence: a query whose live beam entries are all
        #    expanded is done — every reachable unvisited node is worse
        #    than its whole beam
        active = active & jnp.any(~mexp & (mslots >= 0), axis=1)
        return (it + 1, mslots, mv, mexp, nrslots, rv, visited, active,
                hops)

    st = (jnp.int32(0), bslots, bscores, bexp, rslots, rscores, visited,
          active, hops)
    st = lax.while_loop(cond, body, st)
    rslots, visited, hops = st[4], st[6], st[8]
    vcount = (
        jnp.sum(lax.population_count(visited), axis=1)
        - jnp.sum(lax.population_count(inv_words))
    ).astype(jnp.int32)
    occ = jnp.sum((rslots >= 0).astype(jnp.int32), axis=1)
    return rslots, hops, vcount, occ
