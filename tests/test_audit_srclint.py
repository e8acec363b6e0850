"""Layer-2 audit tests: every srclint rule fires on its planted fixture
(no dead rules), near-miss code stays clean, suppression works, and —
the acceptance bar — the shipped package itself lints clean.

The fixtures under tests/audit_fixtures/ are lint inputs only: they are
never imported, and several would crash if they were (that is the
point).
"""

import os

import pytest

from tpu_syncbn.audit import srclint
from tpu_syncbn.audit.srclint import RULES, Violation, lint_file, lint_source

pytestmark = pytest.mark.audit

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "audit_fixtures")

#: rule id -> (fixture file, minimum firing count). Keeping this map in
#: lockstep with RULES is itself a test: a rule without a fixture is
#: dead weight by definition (ISSUE 6).
RULE_FIXTURES = {
    "raw_api_bypass": ("bad_raw_api_bypass.py", 8),
    "host_sync_in_step": ("bad_host_sync_in_step.py", 2),
    "donate_after_use": ("bad_donate_after_use.py", 2),
    "unlocked_shared_state": ("bad_unlocked_shared_state.py", 4),
    "telemetry_name_schema": ("bad_telemetry_name_schema.py", 8),
    "unpaired_trace_span": ("bad_unpaired_trace_span.py", 3),
    "wallclock_duration": ("bad_wallclock_duration.py", 3),
    "unbounded_blocking": ("bad_unbounded_blocking.py", 5),
    "hardcoded_mesh_axis": ("bad_hardcoded_mesh_axis.py", 6),
    "private_mesh_plumbing": ("bad_private_mesh_plumbing.py", 5),
    "lossy_default_mode": ("bad_lossy_default_mode.py", 4),
    "unbounded_label_value": ("bad_unbounded_label_value.py", 5),
}


def _fixture(name: str) -> str:
    return os.path.join(FIXTURE_DIR, name)


class TestEveryRuleFires:
    def test_fixture_map_covers_every_rule(self):
        assert set(RULE_FIXTURES) == set(RULES), (
            "every lint rule needs a planted-violation fixture "
            "(and every fixture a live rule)"
        )

    @pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
    def test_rule_fires_on_its_fixture(self, rule):
        fname, min_hits = RULE_FIXTURES[rule]
        violations = lint_file(_fixture(fname))
        hits = [v for v in violations if v.rule == rule]
        assert len(hits) >= min_hits, (
            f"{rule} found {len(hits)} violation(s) in {fname}, "
            f"expected >= {min_hits}: {[v.format() for v in violations]}"
        )
        # the fixture is single-purpose: no OTHER rule may fire on it
        assert {v.rule for v in violations} == {rule}
        # findings carry usable positions
        for v in hits:
            assert v.line >= 1 and v.path.endswith(fname)

    def test_clean_fixture_has_no_findings(self):
        violations = lint_file(_fixture("clean.py"))
        assert violations == [], [v.format() for v in violations]


class TestPackageClean:
    def test_shipped_package_lints_clean(self):
        """ISSUE 6 satellite: every violation the auditor surfaced in
        the existing stack is fixed (here: none survive)."""
        violations = srclint.lint_package()
        assert violations == [], [v.format() for v in violations]

    def test_package_files_enumerates_the_package(self):
        files = srclint.package_files()
        names = {os.path.basename(f) for f in files}
        assert {"compat.py", "srclint.py", "batcher.py"} <= names
        assert not any("__pycache__" in f for f in files)


class TestSuppression:
    SRC = (
        "from flax import nnx\n"
        "def f(g, p):\n"
        "    return nnx.merge(g, p)  {comment}\n"
    )

    def test_bare_ok_suppresses(self):
        src = self.SRC.format(comment="# audit: ok")
        assert lint_source(src, "x.py") == []

    def test_rule_scoped_ok_suppresses_that_rule(self):
        src = self.SRC.format(comment="# audit: ok[raw_api_bypass]")
        assert lint_source(src, "x.py") == []

    def test_wrong_rule_id_does_not_suppress(self):
        src = self.SRC.format(comment="# audit: ok[host_sync_in_step]")
        vs = lint_source(src, "x.py")
        assert [v.rule for v in vs] == ["raw_api_bypass"]

    def test_fixture_suppression_line_not_reported(self):
        # bad_raw_api_bypass.py ends with a suppressed nnx.merge call
        vs = lint_file(_fixture("bad_raw_api_bypass.py"))
        src_lines = open(_fixture("bad_raw_api_bypass.py")).read().splitlines()
        suppressed_lines = {
            i + 1 for i, l in enumerate(src_lines) if "audit: ok" in l
        }
        assert suppressed_lines, "fixture must exercise suppression"
        assert not {v.line for v in vs} & suppressed_lines


class TestRuleEdges:
    """Near-miss semantics pinned per rule — the false-positive budget
    of a lint is what decides whether anyone keeps running it."""

    def test_donate_rebind_from_result_is_clean(self):
        src = (
            "class T:\n"
            "    def step(self, b):\n"
            "        (self._p, loss) = self._train_step(self._p, b)\n"
            "        return dict(self._p), loss\n"
        )
        assert lint_source(src, "x.py") == []

    def test_donate_read_before_dispatch_is_clean(self):
        src = (
            "class T:\n"
            "    def step(self, b):\n"
            "        snap = dict(self._p)\n"
            "        out = self._train_step(self._p, b)\n"
            "        return out, snap\n"
        )
        assert lint_source(src, "x.py") == []

    def test_donate_rebind_inside_a_with_block_is_clean(self):
        # the trainer's own shape: the dispatch under a tracing span.
        # The ``with`` does not donate on behalf of the assignment
        # nested in it, which rebinds what it donates
        src = (
            "class T:\n"
            "    def step(self, b):\n"
            "        with span('train_step'):\n"
            "            (self._p, loss) = self._train_step(self._p, b)\n"
            "        return dict(self._p), loss\n"
        )
        assert lint_source(src, "x.py") == []

    @pytest.mark.parametrize("block", [
        "with span('snapshot'):", "if b is not None:", "for _ in range(2):",
        "try:",
    ])
    def test_donate_then_read_in_a_nested_block_is_caught(self, block):
        tail = "        finally:\n            pass\n" \
            if block == "try:" else ""
        src = (
            "class T:\n"
            "    def step(self, b):\n"
            "        with span('train_step'):\n"
            "            out = self._train_step(self._p, b)\n"
            f"        {block}\n"
            "            snap = dict(self._p)\n"
            f"{tail}"
            "        return out, snap\n"
        )
        vs = lint_source(src, "x.py")
        assert [(v.rule, v.line) for v in vs] == [("donate_after_use", 6)]

    def test_donating_factory_result_is_tracked(self):
        src = (
            "class T:\n"
            "    def step(self, b):\n"
            "        fn = cached_program(self._cache, 1, self._build)\n"
            "        out = fn(self._p, b)\n"
            "        return out, dict(self._p)\n"
        )
        vs = lint_source(src, "x.py")
        assert [v.rule for v in vs] == ["donate_after_use"]

    def test_raw_import_from_forms_are_flagged(self):
        # `from jax import shard_map` + bare call: the exact pattern the
        # PR 6 sweep fixed in examples/ and benchmarks/
        src = (
            "from jax import shard_map\n"
            "def build(fn, mesh, s):\n"
            "    return shard_map(fn, mesh=mesh, in_specs=s, out_specs=s)\n"
        )
        vs = lint_source(src, "x.py")
        assert [v.rule for v in vs] == ["raw_api_bypass"]
        assert "compat.shard_map" in vs[0].message

    def test_raw_profiler_start_is_flagged(self):
        # ISSUE 14 satellite: a raw jax.profiler.start_trace outside
        # obs/profiling.py fires — the unbounded process-singleton
        # trace must route through the bounded obs.profiling capture
        src = (
            "import jax\n"
            "def prof(d):\n"
            "    jax.profiler.start_trace(d)\n"
        )
        vs = lint_source(src, "tpu_syncbn/utils/metrics.py")
        assert [v.rule for v in vs] == ["raw_api_bypass"]
        assert "obs.profiling" in vs[0].message

    def test_raw_profiler_allowed_in_obs_profiling(self):
        # ...and obs/profiling.py is the one documented home of the raw
        # start/stop calls
        src = (
            "import jax\n"
            "def prof(d):\n"
            "    jax.profiler.start_trace(d)\n"
            "    jax.profiler.stop_trace()\n"
        )
        assert lint_source(src, "tpu_syncbn/obs/profiling.py") == []

    def test_host_sync_in_nested_def_reported_once(self):
        src = (
            "class T:\n"
            "    def _make_step_fn(self):\n"
            "        def step(state, batch):\n"
            "            def inner(x):\n"
            "                return x.item()\n"
            "            return inner(batch)\n"
            "        return step\n"
        )
        vs = lint_source(src, "x.py")
        assert len(vs) == 1 and vs[0].rule == "host_sync_in_step"

    def test_host_sync_outside_step_builder_is_clean(self):
        src = (
            "import numpy as np\n"
            "def driver(x):\n"
            "    return np.asarray(x).mean().item()\n"
        )
        assert lint_source(src, "x.py") == []

    def test_traced_by_name_argument_is_covered(self):
        # a function handed to lax.scan by name is device code even
        # outside a *_step_fn builder
        src = (
            "from jax import lax\n"
            "def body(carry, x):\n"
            "    v = x.item()\n"
            "    return carry, v\n"
            "def run(c, xs):\n"
            "    return lax.scan(body, c, xs)\n"
        )
        vs = lint_source(src, "x.py")
        assert [v.rule for v in vs] == ["host_sync_in_step"]

    def test_lockless_class_containers_are_clean(self):
        src = (
            "class C:\n"
            "    def __init__(self):\n"
            "        self._items = []\n"
            "    def add(self, x):\n"
            "        self._items.append(x)\n"
        )
        assert lint_source(src, "x.py") == []

    def test_locked_counter_bump_is_clean_unlocked_fires(self):
        src = (
            "import threading\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._n = 0\n"
            "    def ok(self):\n"
            "        with self._lock:\n"
            "            self._n += 1\n"
            "    def bad(self):\n"
            "        self._n += 1\n"
        )
        vs = lint_source(src, "x.py")
        assert len(vs) == 1 and vs[0].rule == "unlocked_shared_state"
        assert ".bad" in vs[0].message or "C.bad" in vs[0].message

    def test_counter_group_single_token_prefix_ok(self):
        src = "g = CounterGroup(prefix='serve')\n"
        assert lint_source(src, "x.py") == []

    def test_span_stored_or_entered_is_clean(self):
        src = (
            "def f(tracer):\n"
            "    with tracer.span('a.b'):\n"
            "        pass\n"
            "    s = tracer.span('c.d')\n"
            "    return s\n"
        )
        assert lint_source(src, "x.py") == []

    def test_wallclock_subtraction_fires_monotonic_clean(self):
        """ISSUE 8 satellite: time.time() subtraction is a duration bug
        (wall clock steps under NTP — an alert-engine hazard);
        monotonic/perf_counter subtraction is the sanctioned form."""
        bad = (
            "import time\n"
            "def f():\n"
            "    t0 = time.time()\n"
            "    return time.time() - t0\n"
        )
        vs = lint_source(bad, "x.py")
        assert [v.rule for v in vs] == ["wallclock_duration"]
        assert "monotonic" in vs[0].message
        clean = (
            "import time\n"
            "def f():\n"
            "    t0 = time.perf_counter()\n"
            "    ts = time.time()  # timestamp, never subtracted\n"
            "    return time.perf_counter() - t0, ts\n"
        )
        assert lint_source(clean, "x.py") == []

    def test_wallclock_from_import_and_attr_forms(self):
        # `from time import time` spelling and self-attribute anchors
        # are the same hazard
        src = (
            "from time import time\n"
            "class C:\n"
            "    def __init__(self):\n"
            "        self._t0 = time()\n"
            "    def age(self):\n"
            "        return time() - self._t0\n"
        )
        vs = lint_source(src, "x.py")
        assert [v.rule for v in vs] == ["wallclock_duration"]

    def test_wallclock_binding_does_not_leak_across_functions(self):
        # a wallclock name in one function must not taint an unrelated
        # subtraction of the same name elsewhere
        src = (
            "import time\n"
            "def stamp():\n"
            "    t0 = time.time()\n"
            "    return t0\n"
            "def other(t0, t1):\n"
            "    return t1 - t0\n"
        )
        assert lint_source(src, "x.py") == []

    def test_unknown_subsystem_prefix_fires_known_clean(self):
        """ISSUE 8 satellite: the metric-name vocabulary is closed —
        obs./slo./monitor. (the live-monitoring families) are known,
        a typo'd subsystem is a finding."""
        assert lint_source(
            "telemetry.count('obs.alert.fired')\n"
            "telemetry.count('slo.evaluations')\n"
            "telemetry.set_gauge('monitor.heartbeat_age_s', 1.0)\n",
            "x.py",
        ) == []
        vs = lint_source("telemetry.count('sevre.latency_s')\n", "x.py")
        assert [v.rule for v in vs] == ["telemetry_name_schema"]
        assert "sevre" in vs[0].message

    def test_monitor_metric_pins_satisfy_the_allowance(self):
        """The six pinned live-monitoring names (obs.server.MONITOR_METRICS)
        must all pass the schema+vocabulary rule — the pin and the
        allowance cannot drift apart."""
        from tpu_syncbn.obs.server import MONITOR_METRICS

        assert len(MONITOR_METRICS) == 6
        src = "".join(
            f"telemetry.count({name!r})\n" for name in MONITOR_METRICS
        )
        assert lint_source(src, "x.py") == []

    def test_unbounded_blocking_requires_a_thread_owning_scope(self):
        """ISSUE 9 satellite: the rule only bites where a wedged peer
        thread can hang the subsystem — plain (non-thread-owning) code
        with the same calls is out of scope."""
        src = (
            "import queue\n"
            "q = queue.Queue()\n"
            "def plain_consumer():\n"
            "    return q.get()\n"
            "def plain_join(t):\n"
            "    t.join()\n"
        )
        assert lint_source(src, "x.py", rules=["unbounded_blocking"]) == []

    def test_unbounded_blocking_bounded_and_lookup_forms_clean(self):
        """Timeouts, *_nowait, and the arg-carrying lookalikes
        (dict.get(key), str.join(xs), os.path.join(...)) never fire
        even inside a thread-owning class."""
        src = (
            "import os\n"
            "import queue\n"
            "import threading\n"
            "class Bounded:\n"
            "    def __init__(self):\n"
            "        self._q = queue.Queue(maxsize=2)\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        self._q.get(timeout=1.0)\n"
            "        self._q.get_nowait()\n"
            "        self._q.put(1, timeout=0.5)\n"
            "        self._q.put_nowait(2)\n"
            "    def close(self, cfg, parts):\n"
            "        self._t.join(5.0)\n"
            "        self._t.join(timeout=5.0)\n"
            "        cfg.get('key')\n"
            "        return os.path.join(*parts), ', '.join(parts)\n"
        )
        assert lint_source(src, "x.py", rules=["unbounded_blocking"]) == []

    def test_mesh_axis_constant_import_is_clean(self):
        """ISSUE 10 satellite: the sanctioned spelling — import the
        constant from mesh_axes — never fires, and non-axis uses of the
        same words (dict keys, metric families) stay clean."""
        src = (
            "from jax.sharding import PartitionSpec as P\n"
            "from tpu_syncbn.mesh_axes import DATA_AXIS\n"
            "def spec():\n"
            "    return P(DATA_AXIS)\n"
            "def stats():\n"
            "    return {'data': 1, 'model': 2}\n"
        )
        assert lint_source(src, "x.py",
                           rules=["hardcoded_mesh_axis"]) == []

    def test_mesh_axis_literal_in_constants_module_is_allowed(self):
        src = "DATA_AXIS = 'data'\nMODEL_AXIS = 'model'\n"
        assert lint_source(
            src, "tpu_syncbn/mesh_axes.py",
            rules=["hardcoded_mesh_axis"],
        ) == []
        vs = lint_source(src, "tpu_syncbn/parallel/other.py",
                         rules=["hardcoded_mesh_axis"])
        assert len(vs) == 2

    def test_mesh_axis_default_pairing_handles_posonly_args(self):
        """Review finding: defaults align with the tail of
        posonly+positional args — a positional-only default must not
        shift the pairing in either direction."""
        # 'data' is x's default (not an axis kwarg): clean
        clean = "def f(x='data', /, axis_name=None):\n    return x\n"
        assert lint_source(clean, "x.py",
                           rules=["hardcoded_mesh_axis"]) == []
        # the literal really is axis_name's default: flagged
        bad = "def g(x=1, /, axis_name='data'):\n    return x\n"
        vs = lint_source(bad, "x.py", rules=["hardcoded_mesh_axis"])
        assert len(vs) == 1 and "axis_name" in vs[0].message

    def test_non_policed_axis_names_stay_clean(self):
        # "pipe"/"expert"/"seq" are centralized too, but the rule only
        # polices the item-1 composition axes the ISSUE names
        src = "from jax.sharding import PartitionSpec as P\n" \
              "s = P('pipe')\n"
        assert lint_source(src, "x.py",
                           rules=["hardcoded_mesh_axis"]) == []

    def test_syntax_error_reports_parse_error(self):
        vs = lint_source("def broken(:\n", "x.py")
        assert [v.rule for v in vs] == ["parse_error"]

    def test_rule_subset_selection(self):
        vs = lint_file(
            _fixture("bad_raw_api_bypass.py"),
            rules=["telemetry_name_schema"],
        )
        assert vs == []


class TestViolationObject:
    def test_format_and_json_round_trip(self):
        v = Violation(rule="raw_api_bypass", message="m", path="p.py",
                      line=3, col=7)
        assert v.format() == "p.py:3: [raw_api_bypass] m"
        assert v.to_json() == {
            "rule": "raw_api_bypass", "message": "m", "path": "p.py",
            "line": 3, "col": 7,
        }

    def test_lineless_violation_formats_without_position(self):
        v = Violation(rule="contract.golden_mismatch", message="m",
                      path="<jaxpr>", line=0)
        assert v.format() == "<jaxpr>: [contract.golden_mismatch] m"
