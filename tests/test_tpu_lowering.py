"""Cross-lower every Pallas entry point for TPU, on the CPU.

`jax.export` with `platforms=["tpu"]` runs the Pallas TPU lowering without
a chip, at the shapes `auto` routes real traffic to (b=64, d=768, k=10,
cap=1024, interpret=False). The CPU suite only ever runs these kernels in
interpret mode at 16-128 dimensions, where the lowering's block-shape
rules never apply: two of them were refused at 768-d for a `(b, 1)`
column block until ISSUE 22.

This guards block-shape and cast refusals only. Mosaic's own passes
(vector layouts, VMEM budget) run inside the TPU compiler: those need
`chip_smoke.py`'s kernel phase on a chip — or the slow test at the bottom,
where the installed libtpu can compile for a v5e topology with no chip
attached.
"""

import jax
import jax.numpy as jnp
import pytest

from dingo_tpu.ops import pallas_ivf, pallas_pq, pallas_topk

B, D, K, CAP = 64, 768, 10, 1024
N = 1 << 16             # flat rows (row blocks of 2048)
NB = 96                 # IVF buckets
BUDGET = 49             # nprobe 32 + spill slack (ivf_layout.expand_probes)
DBLK = 128
NBLK = D // DBLK
M, KSUB, NPROBE = 96, 256, 32

f32, i32, u8, bf16 = jnp.float32, jnp.int32, jnp.uint8, jnp.bfloat16


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _codec(sq):
    return (_sds((D,), f32), _sds((D,), f32)) if sq else (None, None)


def _fused_topk():
    return pallas_topk.fused_topk, (
        _sds((B, D), f32), _sds((N, D), f32), _sds((N,), f32),
        _sds((N,), jnp.bool_),
    ), dict(k=K, block=2048, ascending=True, interpret=False)


def _pruned_fused_topk(xdt):
    sq = xdt == u8
    return pallas_topk.pruned_fused_topk, (
        _sds((B, D), f32), _sds((NBLK, N, DBLK), xdt), _sds((NBLK, N), f32),
        _sds((N,), f32), _sds((N,), jnp.bool_), *_codec(sq),
    ), dict(k=K, block=2048, dim_block=DBLK, check_every=1, ascending=True,
            interpret=False, sq=sq, inbucket=True)


def _ivf_list_topk():
    return pallas_ivf.ivf_list_topk, (
        _sds((B, BUDGET), i32), _sds((B, D), f32), _sds((NB, CAP, D), f32),
        _sds((NB, CAP), f32), _sds((NB, CAP), jnp.bool_),
        _sds((NB, CAP), i32),
    ), dict(k=K, ascending=True, interpret=False, nq=B)


def _ivf_pruned_topk(xdt):
    sq = xdt == u8
    return pallas_ivf.ivf_pruned_topk, (
        _sds((B, BUDGET), i32), _sds((B, D), f32), _sds((B, NBLK), f32),
        _sds((NB, CAP, D), xdt), _sds((NB, NBLK, CAP), f32),
        _sds((NB, CAP), f32), _sds((NB, CAP), jnp.bool_),
        _sds((NB, CAP), i32), *_codec(sq),
    ), dict(k=K, dim_block=DBLK, ascending=True, check_every=1,
            interpret=False, nq=B, sq=sq, inbucket=True)


def _ivf_batch_topk(xdt):
    return pallas_ivf.ivf_batch_topk, (
        _sds((B, BUDGET), i32), _sds((B, D), f32), _sds((NB, CAP, D), xdt),
        _sds((NB, CAP), f32), _sds((NB, CAP), jnp.bool_),
        _sds((NB, CAP), i32), *_codec(xdt == u8),
    ), dict(k=K, ascending=True, interpret=False)


def _ivf_pq_adc_scores(cap, budget):
    """The ADC scoring kernel over buckets of `cap` codes and `budget`
    probed buckets a query: cap 32 is `ivfpq768.batch64`'s (122,880 rows
    over 4,096 lists)."""
    return pallas_pq.ivf_pq_adc_scores, (
        _sds((B, budget), i32), _sds((B, budget), i32),
        _sds((M, B, NPROBE, KSUB), f32), _sds((NB, cap, M), u8),
        _sds((NB, cap), jnp.bool_),
    ), dict(interpret=False)


def _ivfpq_search_program():
    """IVF_PQ's whole request program with the Pallas scoring arm, at the
    cell's shapes and shortlist (k' = 10 x 64), the exact rerank
    included."""
    from dingo_tpu.index.ivf_pq import ivfpq_search_program
    from dingo_tpu.ops.distance import Metric

    return ivfpq_search_program, (
        _sds((B, D), f32), _sds((4096, D), f32), _sds((4096,), f32),
        _sds((4096, 2), i32), _sds((NB, 32), jnp.bool_), _sds((NB, 32), i32),
        _sds((NB,), i32), _sds((NB, 32, M), u8), _sds((M, KSUB, D // M), f32),
        _sds((N, D), f32), _sds((N,), f32),
    ), dict(k=K, kscan=640, nprobe=NPROBE, metric=Metric.L2, pallas=True,
            precompute_lut=True, interpret=False)


CASES = {
    "fused_topk": _fused_topk,
    "pruned_fused_topk[f32]": lambda: _pruned_fused_topk(f32),
    "pruned_fused_topk[bf16]": lambda: _pruned_fused_topk(bf16),
    "pruned_fused_topk[sq8]": lambda: _pruned_fused_topk(u8),
    "ivf_list_topk": _ivf_list_topk,
    "ivf_pruned_topk[f32]": lambda: _ivf_pruned_topk(f32),
    "ivf_pruned_topk[bf16]": lambda: _ivf_pruned_topk(bf16),
    "ivf_pruned_topk[sq8]": lambda: _ivf_pruned_topk(u8),
    "ivf_batch_topk[f32]": lambda: _ivf_batch_topk(f32),
    "ivf_batch_topk[bf16]": lambda: _ivf_batch_topk(bf16),
    "ivf_batch_topk[sq8]": lambda: _ivf_batch_topk(u8),
    "ivf_pq_adc_scores[m=96,cap=32]": lambda: _ivf_pq_adc_scores(32, 56),
    "ivf_pq_adc_scores[m=96,cap=1024]": lambda: _ivf_pq_adc_scores(
        CAP, BUDGET),
    "ivfpq_search_program[pallas]": _ivfpq_search_program,
}


def _jitted(case):
    """The raw kernel entry point (under the sentinel wrapper) as a plain
    jax.jit with its statics bound, plus its abstract arguments."""
    entry, args, static = CASES[case]()
    fn = entry.__wrapped__
    return jax.jit(lambda *a: fn(*a, **static)), args


@pytest.mark.parametrize("case", sorted(CASES))
def test_pallas_entry_point_lowers_for_tpu(case):
    fn, args = _jitted(case)
    exported = jax.export.export(fn, platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.slow
@pytest.mark.parametrize("case", sorted(CASES))
def test_pallas_entry_point_compiles_for_v5e(case):
    """Mosaic itself, without a chip: libtpu compiles ahead of time for a
    described v5e topology. Skipped where the installation cannot describe
    one. Seconds per kernel, so outside tier-1."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"no TPU topology for ahead-of-time compilation: {e}")
    on_chip = SingleDeviceSharding(topo.devices[0])
    fn, args = _jitted(case)
    args = [a if a is None else
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
            for a in args]
    fn.lower(*args).compile()


@pytest.mark.slow
def test_pq_encode_compares_in_f32_for_v5e():
    """The residual PQ encode, compiled for a described v5e: no value of
    its nearest-codeword search is carried as bf16, and no argmin is fused
    into the contraction that makes its distances. `jnp.argmin` was (a
    `(bf16, s32)` reduce): 65 % of the codes of `ivfpq768` differed from
    float64's on the chip; fused into the vmapped contraction the int key
    of `ops.distance.nearest_index` read 13 % right (PR 37)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dingo_tpu.index.ivf_pq import _encode_residual

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"no TPU topology for ahead-of-time compilation: {e}")
    on_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
            for shape, dtype in (((8192, D), f32), ((8192,), i32),
                                 ((4096, D), f32), ((M, KSUB, D // M), f32))]
    hlo = _encode_residual._jitted.lower(*args).compile().as_text()
    assert "bf16[" not in hlo
    fused = hlo.split("\n}\n")          # one computation per piece
    argmins = [c for c in fused if "nearest_index/reduce" in c]
    assert argmins and not any("convolution(" in c for c in argmins)


@pytest.mark.slow
def test_pq_adc_carries_no_bf16_for_v5e():
    """IVF_PQ's request program with the Pallas scoring arm, compiled for a
    described v5e: no table entry, partial sum or score is carried in
    bf16, neither in the XLA parts (the LUT, the top-k, the rerank) nor in
    the kernel's own program, whose one contraction (each row's lane sum)
    takes f32 operands at HIGHEST. A bf16 table destroyed the ranking
    (ops/pq.py)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"no TPU topology for ahead-of-time compilation: {e}")
    on_chip = SingleDeviceSharding(topo.devices[0])
    fn, args = _jitted("ivfpq_search_program[pallas]")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
            for a in args]
    hlo = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo and "bf16[" not in hlo
    kernel, kargs = _jitted("ivf_pq_adc_scores[m=96,cap=32]")
    jaxpr = str(jax.make_jaxpr(kernel)(*kargs))
    assert "pallas_call" in jaxpr and "bf16" not in jaxpr
    assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in jaxpr
