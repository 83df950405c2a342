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


def _ivf_pq_adc_topk():
    return pallas_pq.ivf_pq_adc_topk, (
        _sds((B, BUDGET), i32), _sds((B, BUDGET), i32),
        _sds((B, NPROBE, M, KSUB), f32), _sds((NB, CAP, M), u8),
        _sds((NB, CAP), jnp.bool_), _sds((NB, CAP), i32),
    ), dict(k=K, interpret=False, nq=B)


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
    "ivf_pq_adc_topk[m=96]": _ivf_pq_adc_topk,
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
