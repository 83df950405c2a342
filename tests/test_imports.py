"""Every module under dingo_tpu/ must IMPORT.

The `from jax import shard_map` break (jax 0.4.37) silently dropped four
whole test modules from tier-1 as *collection errors* — pytest kept going
and nothing red pointed at the real regression. This test turns any
import-time failure anywhere in the package into one loud assertion with
the module name and error attached, so an API drift or a bad top-level
import can never hide behind --continue-on-collection-errors again.
"""

import importlib
import pkgutil

import dingo_tpu


def test_import_every_module():
    failures = []
    count = 0
    for mod in pkgutil.walk_packages(dingo_tpu.__path__,
                                     prefix="dingo_tpu."):
        name = mod.name
        # native/*.so are ctypes-loaded C artifacts (dingo_tpu/native
        # loads them via CDLL), not Python extension modules — importlib
        # is the wrong door for them by design
        if name.startswith("dingo_tpu.native.lib"):
            continue
        count += 1
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — the point is the report
            failures.append(f"{name}: {e!r}")
    assert count > 80, f"package walk looks broken (only {count} modules)"
    assert not failures, "import-time regressions:\n" + "\n".join(failures)


def test_sharded_modules_import():
    """The four modules a shard_map break takes down (they import
    jax.shard_map directly), pinned by name so a regression names the
    exact culprit."""
    for name in (
        "dingo_tpu.parallel.sharded_store",
        "dingo_tpu.parallel.sharded_flat",
        "dingo_tpu.parallel.sharded_ivf",
        "dingo_tpu.parallel.sharded_pq",
    ):
        importlib.import_module(name)
