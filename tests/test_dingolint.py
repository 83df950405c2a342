"""dingolint (tools/dingolint/) wired as a tier-1 gate.

Per checker: a known-bad fixture snippet fires, a known-good snippet
stays clean, and inline suppression is honored. Plus the tier-1 teeth:
a whole-repo run must produce ZERO unbaselined findings (every baseline
entry carrying a real rationale) and stay fast enough to live in tier-1.
"""

import importlib
import json
import textwrap

import pytest

core = importlib.import_module("tools.dingolint.core")
bl = importlib.import_module("tools.dingolint.baseline")
lint_cli = importlib.import_module("tools.lint")

from tools.dingolint.checkers.bare_jit import BareJitChecker
from tools.dingolint.checkers.context_handoff import ContextHandoffChecker
from tools.dingolint.checkers.host_sync import HostSyncChecker
from tools.dingolint.checkers.knob_audit import KnobAuditChecker
from tools.dingolint.checkers.ladder_shape import LadderShapeChecker
from tools.dingolint.checkers.lock_order import LockOrderChecker
from tools.dingolint.checkers.metric_names import MetricNamesChecker
from tools.dingolint.checkers.resolve_sync import ResolveSyncChecker


def _lint(tmp_path, rel, source, checker, root_rel=None):
    """Write one fixture module and run one checker over it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    repo = core.load_paths([str(path)], root=str(tmp_path))
    return core.run_checkers(repo, [checker])


# -- lock-order --------------------------------------------------------------

_LOCK_CYCLE = """
    import threading

    class Plane:
        def __init__(self):
            self._lock = threading.Lock()

        def observe(self):
            with self._lock:
                with self.store.device_lock:
                    pass

        def mutate(self):
            with self.store.device_lock:
                with self._lock:
                    pass
"""


def test_lock_order_flags_cycle(tmp_path):
    findings = _lint(tmp_path, "plane.py", _LOCK_CYCLE, LockOrderChecker())
    assert len(findings) == 1
    assert "cycle" in findings[0].message
    assert "store.device_lock" in findings[0].message


def test_lock_order_consistent_nesting_clean(tmp_path):
    good = _LOCK_CYCLE.replace(
        "with self.store.device_lock:\n                with self._lock:",
        "with self.store.device_lock:\n                with self.noop:",
    )
    assert _lint(tmp_path, "plane.py", good, LockOrderChecker()) == []


def test_lock_order_flags_plain_lock_self_deadlock(tmp_path):
    src = """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """
    findings = _lint(tmp_path, "a.py", src, LockOrderChecker())
    assert len(findings) == 1 and "re-acquired" in findings[0].message


def test_lock_order_rlock_reentry_clean(tmp_path):
    src = """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self.inner()

            def inner(self):
                with self._lock:
                    pass
    """
    assert _lint(tmp_path, "a.py", src, LockOrderChecker()) == []


def test_lock_order_known_order_reversal(tmp_path):
    src = """
        import threading

        class VectorIndexWrapper:
            def __init__(self):
                self._lock = threading.RLock()

            def backwards(self):
                with self.store.device_lock:
                    with self._lock:
                        pass
    """
    findings = _lint(tmp_path, "wrapper.py", src, LockOrderChecker())
    assert len(findings) == 1 and "reversal" in findings[0].message


def test_lock_order_edge_through_mutual_recursion(tmp_path):
    # a recursive-memo implementation cached incomplete closures for
    # call-graph cycle members and dropped their lock edges entirely
    src = """
        import threading

        class A:
            def __init__(self):
                self._lock = threading.Lock()

            def ping(self, n):
                if n:
                    self.pong(n - 1)
                with self._lock:
                    pass

            def pong(self, n):
                self.ping(n)

            def outer(self):
                with self.store.device_lock:
                    self.pong(3)

            def inner(self):
                with self._lock:
                    with self.store.device_lock:
                        pass
    """
    findings = _lint(tmp_path, "a.py", src, LockOrderChecker())
    assert len(findings) == 1 and "cycle" in findings[0].message


# -- host-sync ---------------------------------------------------------------

_HOT_SYNC = """
    import jax
    import numpy as np

    class Idx:
        def search_async(self, queries, topk):
            d = self._kernel(queries)
            vals = jax.device_get(d)        # BAD: sync at dispatch
            if self.span.sampled:
                jax.block_until_ready(d)    # BAD: a sampled request syncs

            def resolve():
                return jax.device_get(d)    # ok: designated sync point

            return resolve
"""


def test_host_sync_flags_dispatch_sync(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/index/bad.py", _HOT_SYNC,
                     HostSyncChecker())
    # a sampled-trace guard sanctions nothing: a traced request has to
    # make the device calls an untraced one makes
    assert [f.lineno for f in findings] == [8, 10]
    assert "device_get" in findings[0].message
    assert "block_until_ready" in findings[1].message


def test_host_sync_resolve_and_guard_clean(tmp_path):
    good = _HOT_SYNC.replace(
        "vals = jax.device_get(d)        # BAD: sync at dispatch",
        "vals = d",
    ).replace(
        "jax.block_until_ready(d)    # BAD: a sampled request syncs",
        "self.span.set_attr('k', 1)",
    )
    assert _lint(tmp_path, "dingo_tpu/index/good.py", good,
                 HostSyncChecker()) == []


def test_host_sync_hidden_cast_flagged(tmp_path):
    src = """
        import jax.numpy as jnp
        import numpy as np

        class Idx:
            def search_async(self, queries):
                d = jnp.dot(queries, self.vecs)
                host = np.asarray(d)          # hidden device_get
                return host
    """
    findings = _lint(tmp_path, "dingo_tpu/index/cast.py", src,
                     HostSyncChecker())
    assert len(findings) == 1 and "hidden" in findings[0].message


def test_host_sync_outside_search_modules_ignored(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/metrics/x.py", _HOT_SYNC,
                     HostSyncChecker())
    assert findings == []


# -- serving-edge cache admission path (host-sync + resolve-sync) ------------
# the cache package roots WHOLESALE (every def, not just search*): a
# lookup runs on the caller thread before QoS queuing, so any device
# sync there stalls admission itself

_CACHE_SYNC = """
    import jax
    import numpy as np

    def lookup(region_id, fp, version):
        probe = jax.device_get(_table[fp])   # BAD: sync at admission
        return probe

    def host_only_lookup(region_id, fp):
        return _table.get((region_id, fp))
"""


def test_host_sync_roots_cache_modules(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/cache/bad.py", _CACHE_SYNC,
                     HostSyncChecker())
    assert len(findings) == 1
    assert "device_get" in findings[0].message


def test_host_sync_cache_hidden_cast_flagged(tmp_path):
    src = """
        import jax.numpy as jnp
        import numpy as np

        def fingerprint(queries):
            d = jnp.sum(queries, axis=1)
            return np.asarray(d)             # hidden device_get
    """
    findings = _lint(tmp_path, "dingo_tpu/cache/cast.py", src,
                     HostSyncChecker())
    assert len(findings) == 1 and "hidden" in findings[0].message


def test_host_sync_cache_host_only_clean(tmp_path):
    good = _CACHE_SYNC.replace(
        "probe = jax.device_get(_table[fp])   # BAD: sync at admission",
        "probe = _table[fp]",
    )
    assert _lint(tmp_path, "dingo_tpu/cache/good.py", good,
                 HostSyncChecker()) == []


def test_resolve_sync_flags_cache_admission_sync(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/cache/bad.py", _CACHE_SYNC,
                     ResolveSyncChecker())
    assert len(findings) == 1
    assert "serving-edge cache" in findings[0].message


# -- resolve-sync ------------------------------------------------------------

_TWO_SYNC_RESOLVE = """
    import jax

    class Idx:
        def search_async(self, queries, topk):
            fetch = self._dispatch(queries)

            def resolve():
                dists = jax.device_get(fetch)
                extra = jax.device_get(self._stats)   # BAD: second sync
                return dists, extra

            return resolve
"""


def test_resolve_sync_flags_second_device_get(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/index/bad.py",
                     _TWO_SYNC_RESOLVE, ResolveSyncChecker())
    assert len(findings) == 1
    assert "second jax.device_get" in findings[0].message
    assert findings[0].symbol.endswith("resolve")


def test_resolve_sync_branch_exclusive_arms_clean(tmp_path):
    src = """
        import jax

        class Idx:
            def search_async(self, queries, topk, rerank):
                fetch = self._dispatch(queries)

                def resolve():
                    if rerank:
                        return jax.device_get(fetch)[0]
                    else:
                        return jax.device_get(fetch)

                return resolve
    """
    assert _lint(tmp_path, "dingo_tpu/index/arms.py", src,
                 ResolveSyncChecker()) == []


def test_resolve_sync_flags_block_until_ready(tmp_path):
    src = """
        import jax

        class Idx:
            def search_async(self, queries):
                fetch = self._dispatch(queries)

                def resolve():
                    jax.block_until_ready(fetch)   # BAD: fetch IS the wait
                    return jax.device_get(fetch)

                return resolve
    """
    findings = _lint(tmp_path, "dingo_tpu/index/blk.py", src,
                     ResolveSyncChecker())
    assert len(findings) == 1
    assert "block_until_ready" in findings[0].message


def test_resolve_sync_flags_reachable_helper(tmp_path):
    src = """
        import jax

        def _note_stats(arr):
            host = jax.device_get(arr)      # BAD: sync under resolve()
            return host.sum()

        class Idx:
            def search_async(self, queries):
                fetch = self._dispatch(queries)

                def resolve():
                    out = jax.device_get(fetch)
                    _note_stats(self._stats)
                    return out

                return resolve
    """
    findings = _lint(tmp_path, "dingo_tpu/index/helper.py", src,
                     ResolveSyncChecker())
    assert len(findings) == 1
    assert "helper reachable from resolve" in findings[0].message
    assert findings[0].symbol == "_note_stats"


def test_resolve_sync_flags_coalescer_flush_thread(tmp_path):
    src = """
        import jax

        class SearchCoalescer:
            def _dispatch(self, key, batch):
                thunk = self.dispatch_fn(key, batch)
                return jax.device_get(thunk)   # BAD: sync on flush thread

        class _Handoff:
            def resolve(self):
                return jax.device_get(self.thunk())   # ok: completion lane
    """
    findings = _lint(tmp_path, "dingo_tpu/common/coal.py", src,
                     ResolveSyncChecker())
    assert len(findings) == 1
    assert "SearchCoalescer" in findings[0].message
    assert findings[0].symbol == "SearchCoalescer._dispatch"


def test_resolve_sync_outside_index_modules_ignored(tmp_path):
    findings = _lint(tmp_path, "dingo_tpu/obs/x.py", _TWO_SYNC_RESOLVE,
                     ResolveSyncChecker())
    assert findings == []


# -- bare-jit ----------------------------------------------------------------

def test_bare_jit_flags_inline_jit(tmp_path):
    src = """
        import jax

        def grow(v):
            return jax.jit(lambda x: x * 2)(v)
    """
    findings = _lint(tmp_path, "m.py", src, BareJitChecker())
    assert len(findings) == 1 and "sentinel_jit" in findings[0].message


def test_bare_jit_pallas_needs_sentinel(tmp_path):
    src = """
        from jax.experimental import pallas as pl
        from dingo_tpu.obs.sentinel import sentinel_jit

        def naked(x):
            return pl.pallas_call(kernel)(x)

        @sentinel_jit("ops.t", static_argnames=("k",))
        def wrapped(x, k):
            return pl.pallas_call(kernel)(x)
    """
    findings = _lint(tmp_path, "m.py", src, BareJitChecker())
    assert len(findings) == 1
    assert findings[0].symbol == "naked"


def test_bare_jit_decorator_and_from_import_forms(tmp_path):
    src = """
        import jax
        from jax import jit

        @jax.jit
        def a(x):
            return x

        @jax.jit(static_argnums=0)
        def b(k, x):
            return x

        def c(v):
            return jit(lambda x: x)(v)
    """
    findings = _lint(tmp_path, "m.py", src, BareJitChecker())
    assert [f.symbol for f in findings] == ["a", "b", "c"]


def test_bare_jit_sharding_kwargs_not_marked_wrapped(tmp_path):
    # Names appearing only inside sentinel_jit kwargs (sharding
    # constructors) must NOT exempt same-named functions
    src = """
        from jax.experimental import pallas as pl
        from dingo_tpu.obs.sentinel import sentinel_jit

        class S:
            def build(self, fn):
                self._jit = sentinel_jit(
                    "k", fn, out_shardings=NamedSharding(mesh, P()))

        def NamedSharding(m, p):
            return pl.pallas_call(kernel)(m)
    """
    findings = _lint(tmp_path, "m.py", src, BareJitChecker())
    assert len(findings) == 1 and findings[0].symbol == "NamedSharding"


def test_bare_jit_suppression_honored(tmp_path):
    src = """
        import jax

        def grow(v):
            # dingolint: ok[bare-jit] one-shot startup reshard
            return jax.jit(lambda x: x * 2)(v)
    """
    assert _lint(tmp_path, "m.py", src, BareJitChecker()) == []


# -- ladder-shape ------------------------------------------------------------

_LADDER = """
    from dingo_tpu.obs.sentinel import sentinel_jit
    from dingo_tpu.index.slot_store import _next_pow2

    @sentinel_jit("ops.t.kern", static_argnames=("k",))
    def kern(x, k):
        return x[:k]

    def bad_direct(q):
        return kern(q, k=len(q))

    def bad_one_hop(q):
        b = q.shape[0]
        return kern(q, b)

    def good_ladder(q):
        return kern(q, k=_next_pow2(len(q)))

    def good_passthrough(q, k):
        return kern(q, k=k)
"""


def test_ladder_shape_flags_data_minted_static_args(tmp_path):
    findings = _lint(tmp_path, "m.py", _LADDER, LadderShapeChecker())
    assert [f.symbol for f in findings] == ["bad_direct", "bad_one_hop"]
    assert all("ladder" in f.message for f in findings)
    # positional AND kwarg forms both resolved to the static name
    assert all("'k'" in f.message for f in findings)


def test_ladder_shape_call_form_wrapper(tmp_path):
    src = """
        from dingo_tpu.obs.sentinel import sentinel_jit

        def _search(x, k):
            return x[:k]

        class S:
            def __init__(self):
                self._search_jit = sentinel_jit(
                    "parallel.t.search", _search, static_argnames=("k",))

            def go(self, q):
                return self._search_jit(q, k=q.shape[0])
    """
    findings = _lint(tmp_path, "m.py", src, LadderShapeChecker())
    assert len(findings) == 1 and findings[0].symbol == "S.go"


# -- context-handoff ---------------------------------------------------------

def test_context_handoff_flags_bare_thread(tmp_path):
    src = """
        import threading

        def loop():
            pass

        def serve():
            threading.Thread(target=loop, daemon=True).start()
    """
    findings = _lint(tmp_path, "m.py", src, ContextHandoffChecker())
    assert len(findings) == 1 and "contextvars" in findings[0].message


def test_context_handoff_capture_evidence_passes(tmp_path):
    src = """
        import threading

        def run(entry):
            token = entry.span.attach()

        def serve():
            threading.Thread(target=run, daemon=True).start()
    """
    assert _lint(tmp_path, "m.py", src, ContextHandoffChecker()) == []


def test_context_handoff_one_delegation_hop(tmp_path):
    src = """
        import threading

        def worker(entry):
            token = entry.span.attach()

        def loop():
            while True:
                worker(next_entry())

        def serve():
            threading.Thread(target=loop, daemon=True).start()
    """
    assert _lint(tmp_path, "m.py", src, ContextHandoffChecker()) == []


def test_context_handoff_suppression_honored(tmp_path):
    src = """
        import threading

        def loop():
            pass

        def serve():
            # dingolint: ok[context-handoff] background poller
            threading.Thread(target=loop, daemon=True).start()
    """
    assert _lint(tmp_path, "m.py", src, ContextHandoffChecker()) == []


# -- metric-names (framework integration; the standalone surface keeps its
#    own tests in test_metrics_names.py) -------------------------------------

def test_metric_names_checker_in_framework(tmp_path):
    src = """
        from dingo_tpu.common.metrics import METRICS

        def f():
            METRICS.counter('CamelCase.Bad').add(1)
            METRICS.counter('xla.rogue_series').add(1)
            METRICS.counter('xla.recompiles').add(1)
    """
    findings = _lint(tmp_path, "m.py", src, MetricNamesChecker())
    assert len(findings) == 2
    assert findings[0].symbol == "f"


def test_metric_names_shim_still_works():
    shim = importlib.import_module("tools.check_metrics_names")
    assert shim.check_file is not None and shim.FAMILY_NAMES


# -- knob-audit --------------------------------------------------------------

def test_knob_audit_flags_unevented_tuning_write(tmp_path):
    src = """
        def sneak(index):
            index.tuning["nprobe"] = 64
    """
    findings = _lint(tmp_path, "dingo_tpu/sneak.py", src,
                     KnobAuditChecker())
    assert len(findings) == 1
    assert "tuning override write" in findings[0].message
    assert findings[0].symbol == "sneak"


def test_knob_audit_emit_in_same_function_is_clean(tmp_path):
    src = """
        from dingo_tpu.obs.events import EVENTS

        def step(index, rid):
            index.tuning["nprobe"] = 64
            EVENTS.emit("tuner", rid, "nprobe", 128, 64, trigger="slo")
    """
    assert _lint(tmp_path, "dingo_tpu/t.py", src, KnobAuditChecker()) == []


def test_knob_audit_exact_caller_coverage(tmp_path):
    # the writer has no emit itself, but its exact caller does — the
    # decision and its record one frame apart is the shed-controller
    # shape and must stay clean
    src = """
        from dingo_tpu.obs.events import EVENTS

        class Shed:
            def _apply(self, index, level):
                index.tuning["nprobe"] = 32
                index.tuning.pop("ef", None)

            def step(self, index, rid, level):
                self._apply(index, level)
                EVENTS.emit("shed", rid, "degrade_level", 0, level,
                            trigger="pressure")
    """
    assert _lint(tmp_path, "dingo_tpu/s.py", src, KnobAuditChecker()) == []


def test_knob_audit_flags_unreachable_writer_and_pop(tmp_path):
    # same writer, but nobody emitting ever calls it
    src = """
        class Shed:
            def _apply(self, index, level):
                index.tuning["nprobe"] = 32
                index.tuning.pop("ef", None)
    """
    findings = _lint(tmp_path, "dingo_tpu/s.py", src, KnobAuditChecker())
    assert len(findings) == 2
    assert {f.message.split(" without")[0] for f in findings} == {
        "tuning override write", "tuning override removal"}


def test_knob_audit_rung_assign_semantics(tmp_path):
    # actuation path flagged; __init__/reset construction exempt
    src = """
        class TierState:
            def __init__(self):
                self.rung = 0

            def reset(self):
                self.rung = 0

            def demote(self, st):
                st.rung = 2
    """
    findings = _lint(tmp_path, "dingo_tpu/tier.py", src,
                     KnobAuditChecker())
    assert len(findings) == 1
    assert "tier rung move" in findings[0].message
    assert findings[0].symbol == "TierState.demote"


def test_knob_audit_advisory_gauge_set_vs_read(tmp_path):
    # setting the advisory gauge is an actuation; reading it is not
    src = """
        def advise(reg, rid):
            reg.gauge("qos.precision_advisory", rid).set(1)

        def observe(reg, rid):
            return reg.gauge("qos.precision_advisory", rid).get()
    """
    findings = _lint(tmp_path, "dingo_tpu/adv.py", src,
                     KnobAuditChecker())
    assert len(findings) == 1
    assert "precision advisory set" in findings[0].message
    assert findings[0].symbol == "advise"


def test_knob_audit_inline_suppression(tmp_path):
    src = """
        def seam(index):
            index.tuning["nprobe"] = 8  # dingolint: ok[knob-audit] test seam
    """
    assert _lint(tmp_path, "dingo_tpu/seam.py", src,
                 KnobAuditChecker()) == []


# -- baseline mechanics ------------------------------------------------------

def _finding():
    return core.Finding("bare-jit", "dingo_tpu/x.py", 3, "f", "msg")


def test_baseline_match_suppresses_and_todo_fails():
    f = _finding()
    entry = {"fingerprint": f.fingerprint, "checker": f.checker,
             "location": "dingo_tpu/x.py:f", "message": f.message,
             "rationale": "TODO: adjudicate"}
    new, matched, unrat, stale = bl.split([f], {f.fingerprint: entry})
    assert new == [] and matched == [f]
    assert unrat == [entry]        # placeholder rationale still fails
    entry["rationale"] = "one-shot startup program"
    new, matched, unrat, stale = bl.split([f], {f.fingerprint: entry})
    assert unrat == [] and stale == []


def test_baseline_stale_entry_reported():
    entry = {"fingerprint": "deadbeef0000", "checker": "bare-jit",
             "location": "gone.py:f", "message": "m", "rationale": "r"}
    new, matched, unrat, stale = bl.split([], {"deadbeef0000": entry})
    assert stale == [entry] and new == [] and unrat == []


def test_fingerprint_ignores_line_numbers():
    a = core.Finding("bare-jit", "p.py", 10, "f", "msg")
    b = core.Finding("bare-jit", "p.py", 99, "f", "msg")
    assert a.fingerprint == b.fingerprint


# -- tier-1 teeth: the whole repo is lint-clean ------------------------------

@pytest.fixture(scope="module")
def repo_run():
    repo, findings = core.lint_repo()
    return repo, findings


def test_repo_zero_unbaselined_findings(repo_run):
    _repo, findings = repo_run
    base = bl.load()
    new, _matched, unrat, _stale = bl.split(findings, base)
    assert new == [], "unbaselined findings:\n" + "\n".join(
        f.render() for f in new)
    assert unrat == [], "baseline entries without rationale: " + str(
        [e["fingerprint"] for e in unrat])


def test_repo_baseline_entries_all_carry_rationale():
    for entry in bl.load().values():
        r = entry.get("rationale", "")
        assert r and not r.startswith("TODO"), entry["fingerprint"]


def test_repo_lint_stays_tier1_viable():
    import time

    t0 = time.monotonic()
    lint_cli.main(["--checker", "metric-names"])
    # the full run is covered by repo_run; a single-checker pass must be
    # cheap and the CLI JSON mode must report wall time under the budget
    assert time.monotonic() - t0 < 30.0


def test_cli_json_mode(capsys):
    rc = lint_cli.main(["--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"] is True
    assert out["wall_s"] < 30.0
    assert len(out["checkers"]) == 9
    assert out["findings"] == []
    assert len(out["baselined"]) >= 1


def test_cli_partial_baseline_update_preserves_other_checkers(tmp_path,
                                                              capsys):
    # --baseline-update with --checker must not delete the other
    # checkers' adjudicated entries (and their rationales)
    alt = tmp_path / "baseline.json"
    alt.write_text(json.dumps(json.load(open(bl.BASELINE_PATH))))
    rc = lint_cli.main(["--baseline-update", "--checker", "bare-jit",
                        "--baseline", str(alt)])
    capsys.readouterr()
    assert rc == 0
    after = bl.load(str(alt))
    shipped = bl.load()
    assert set(after) == set(shipped)
    assert all(after[fp]["rationale"] == shipped[fp]["rationale"]
               for fp in shipped)


def test_cli_baseline_update_roundtrip(tmp_path, capsys):
    alt = tmp_path / "baseline.json"
    rc = lint_cli.main(["--baseline-update", "--baseline", str(alt)])
    capsys.readouterr()
    assert rc == 0
    fresh = bl.load(str(alt))
    shipped = bl.load()
    assert set(fresh) == set(shipped)
    # a fresh adjudication starts as TODO and therefore FAILS the lint
    assert all(e["rationale"] == bl.TODO_RATIONALE
               for e in fresh.values())
    rc = lint_cli.main(["--baseline", str(alt)])
    capsys.readouterr()
    assert rc == 1
