"""The observability subsystem (tpu_syncbn.obs): telemetry registry
semantics, Chrome-trace span mechanics, the disabled-path cost contract,
multi-host export merging, and the on-device step monitors riding
``StepOutput``.

Reference parity note: the torch recipe's observability is rank-0
printing (reference ``README.md:9``) — everything here is OUR
measurement substrate (docs/OBSERVABILITY.md), so its semantics are
pinned directly.
"""

import gc
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import nnx

from tpu_syncbn import nn as tnn, parallel, utils
from tpu_syncbn.obs import stepstats, telemetry, tracing


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts with telemetry at its env default, an empty
    process registry, and no installed tracer — and leaves it that way."""
    telemetry.set_enabled(None)
    telemetry.REGISTRY.reset()
    tracing.uninstall()
    yield
    telemetry.set_enabled(None)
    telemetry.REGISTRY.reset()
    tracing.uninstall()


@pytest.fixture
def capture(tmp_path, monkeypatch):
    """``start()`` / ``stop()`` of a ``jax.profiler`` capture with its
    own tracers off (quick; the switch only needs the session), stopped
    whatever the test does. The module forgets earlier captures first
    (other test files of this process may have made some)."""
    import jax

    monkeypatch.setattr(tracing, "_capture", None)
    monkeypatch.setattr(tracing, "_capture_session", None)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    running = []

    class Capture:
        def start(self):
            jax.profiler.start_trace(str(tmp_path), profiler_options=options)
            running.append(True)

        def stop(self):
            if running:
                running.pop()
                jax.profiler.stop_trace()

    c = Capture()
    yield c
    c.stop()


# ------------------------------------------------------------- instruments


class TestCounterGaugeHistogram:
    def test_counter_monotonic(self):
        r = telemetry.Registry()
        c = r.counter("x")
        assert c.inc() == 1
        assert c.inc(4) == 5
        assert c.value == 5
        assert r.counter("x") is c  # same instrument on re-lookup

    def test_gauge_last_write_wins(self):
        r = telemetry.Registry()
        g = r.gauge("q")
        g.set(3)
        g.set(1.5)
        assert g.value == 1.5

    def test_gauge_inc_dec(self):
        """ISSUE 8 satellite: level gauges (queue depth, in-flight)
        need atomic adjust — read-modify-write via set() loses updates
        under concurrency."""
        r = telemetry.Registry()
        g = r.gauge("q")
        assert g.inc() == 1.0
        assert g.inc(2.5) == 3.5
        assert g.dec(0.5) == 3.0
        assert g.value == 3.0

    def test_gauge_inc_dec_thread_safety(self):
        r = telemetry.Registry()
        g = r.gauge("inflight")

        def work():
            for _ in range(1000):
                g.inc()
                g.dec()

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # balanced inc/dec across 8 racing threads nets exactly zero —
        # the set()-based RMW this replaces would drift
        assert g.value == 0.0

    def test_inc_gauge_helper_gated(self, monkeypatch):
        monkeypatch.delenv("TPU_SYNCBN_TELEMETRY", raising=False)
        telemetry.set_enabled(None)
        telemetry.inc_gauge("serve.inflight")
        assert len(telemetry.REGISTRY) == 0
        telemetry.set_enabled(True)
        telemetry.inc_gauge("serve.inflight", 2)
        telemetry.inc_gauge("serve.inflight", -1)
        assert telemetry.REGISTRY.gauge("serve.inflight").value == 1.0

    def test_histogram_bucketing(self):
        r = telemetry.Registry()
        h = r.histogram("h", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 1.0, 5.0, 100.0):
            h.observe(v)
        s = h.snapshot()
        # <=0.1 | <=1.0 | <=10.0 | overflow — boundary values land in
        # their "<=" bucket
        assert s["counts"] == [2, 2, 1, 1]
        assert s["count"] == 6 and s["min"] == 0.05 and s["max"] == 100.0
        assert s["sum"] == pytest.approx(106.65)

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError, match="increasing"):
            telemetry.Histogram("h", buckets=(1.0, 1.0))

    def test_kind_clash_is_loud(self):
        r = telemetry.Registry()
        r.counter("name")
        with pytest.raises(ValueError, match="already a counter"):
            r.gauge("name")

    def test_snapshot_schema_validates(self):
        r = telemetry.Registry()
        r.counter("c").inc()
        r.gauge("g").set(1.0)
        r.histogram("h").observe(0.2)
        snap = telemetry.validate_snapshot(r.snapshot())
        assert snap["counters"]["c"] == 1
        # and the validator is not a rubber stamp
        bad = r.snapshot()
        bad["histograms"]["h"]["count"] = 99
        with pytest.raises(ValueError, match="count"):
            telemetry.validate_snapshot(bad)

    def test_counter_thread_safety(self):
        r = telemetry.Registry()
        c = r.counter("n")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000


# ---------------------------------------------------------- enable gating


class TestDisabledPath:
    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("TPU_SYNCBN_TELEMETRY", raising=False)
        telemetry.set_enabled(None)
        assert not telemetry.enabled()
        monkeypatch.setenv("TPU_SYNCBN_TELEMETRY", "1")
        telemetry.set_enabled(None)  # re-read env
        assert telemetry.enabled()

    def test_disabled_ops_touch_nothing(self, monkeypatch):
        monkeypatch.delenv("TPU_SYNCBN_TELEMETRY", raising=False)
        telemetry.set_enabled(None)
        telemetry.count("a")
        telemetry.set_gauge("b", 1.0)
        telemetry.observe("c", 0.5)
        with telemetry.timed("d"):
            pass
        assert len(telemetry.REGISTRY) == 0

    def test_disabled_overhead_guard(self, monkeypatch):
        """The hot-path contract: registry helpers must stay cheap with
        TPU_SYNCBN_TELEMETRY unset — bounded here at 200k no-op calls
        in well under a second (a real regression, e.g. creating
        instruments or taking locks when disabled, is an order of
        magnitude slower)."""
        monkeypatch.delenv("TPU_SYNCBN_TELEMETRY", raising=False)
        telemetry.set_enabled(None)
        t0 = time.perf_counter()
        for _ in range(200_000):
            telemetry.count("hot")
        dt = time.perf_counter() - t0
        assert len(telemetry.REGISTRY) == 0
        assert dt < 2.0, f"disabled-path count() took {dt:.2f}s for 200k calls"

    def test_enabled_ops_record(self):
        telemetry.set_enabled(True)
        telemetry.count("a", 2)
        telemetry.observe("lat", 0.01)
        snap = telemetry.snapshot()
        assert snap["counters"]["a"] == 2
        assert snap["histograms"]["lat"]["count"] == 1


# -------------------------------------------------------- counter groups


class TestCounterGroup:
    def test_eventcounter_is_countergroup_alias(self):
        assert issubclass(utils.EventCounter, telemetry.CounterGroup)
        with pytest.warns(DeprecationWarning, match="CounterGroup"):
            c = utils.EventCounter()
        assert c.bump("x") == 1 and c.bump("x", 2) == 3
        assert c.count("y") == 0
        assert c.summary() == {"x": 3}

    def test_group_counts_without_telemetry(self):
        telemetry.set_enabled(False)
        g = telemetry.CounterGroup("resilience")
        g.bump("restores")
        assert g.count("restores") == 1  # local counts unconditional
        assert len(telemetry.REGISTRY) == 0  # no mirror when disabled

    def test_group_mirrors_into_registry_when_enabled(self):
        telemetry.set_enabled(True)
        g = telemetry.CounterGroup("resilience")
        g.bump("restores", 3)
        assert telemetry.REGISTRY.counter("resilience.restores").value == 3


# ------------------------------------------------------------- tracing


class TestTracing:
    def test_span_nesting_and_ids(self):
        t = tracing.Tracer()
        with t.span("outer") as outer_id:
            assert t.current_span_id() == outer_id
            assert t.latest_open_span_id() == outer_id
            with t.span("inner", step=3) as inner_id:
                assert inner_id != outer_id
                assert t.current_span_id() == inner_id
                assert t.latest_open_span_id() == inner_id
        assert t.current_span_id() is None
        assert t.latest_open_span_id() is None
        by_name = {e["name"]: e for e in t.events}
        assert by_name["inner"]["args"]["parent_id"] == outer_id
        assert by_name["inner"]["args"]["step"] == 3
        assert "parent_id" not in by_name["outer"]["args"]
        # inner closed first, so it is appended first
        assert [e["name"] for e in t.events] == ["inner", "outer"]

    def test_trace_file_is_valid_chrome_trace_json(self, tmp_path):
        t = tracing.Tracer()
        with t.span("step"):
            with t.span("data_wait"):
                pass
        t.instant("watchdog_stall", span_id=1)
        p = str(tmp_path / "trace.json")
        t.save(p)
        doc = json.loads(open(p).read())  # plain JSON, no trailing junk
        assert isinstance(doc["traceEvents"], list)
        events = tracing.validate_trace(tracing.load_trace(p))
        names = {e["name"] for e in events}
        assert {"step", "data_wait", "watchdog_stall"} <= names
        for e in events:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0

    def test_module_span_is_noop_without_tracer(self):
        # no tracer installed: a shared null context, no events anywhere
        with tracing.span("x"):
            assert tracing.current_span_id() is None
        assert tracing.latest_open_span_id() is None

    def test_install_uninstall_roundtrip(self):
        t = tracing.install()
        with tracing.span("a") as sid:
            assert sid is not None
        assert tracing.uninstall() is t
        assert tracing.get() is None
        assert [e["name"] for e in t.events] == ["a"]

    def test_spans_survive_exceptions(self):
        t = tracing.Tracer()
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        assert t.events[0]["name"] == "boom"
        assert t.latest_open_span_id() is None


class TestSpanClocks:
    """What a span records besides its name: wall time on
    ``time.perf_counter`` and its thread's CPU time."""

    def test_a_sleeping_span_has_little_cpu_time_a_busy_one_nearly_all(self):
        t = tracing.Tracer()
        with t.span("asleep"):
            time.sleep(0.05)
        with t.span("busy"):
            end = time.perf_counter() + 0.05
            while time.perf_counter() < end:
                pass
        by_name = {e["name"]: e for e in t.events}
        asleep, busy = by_name["asleep"], by_name["busy"]
        assert asleep["dur"] >= 50_000
        assert asleep["args"]["cpu_us"] < 0.2 * asleep["dur"]
        # a loaded test machine may take the core away for a while
        assert 0.5 * busy["dur"] < busy["args"]["cpu_us"] <= 1.05 * busy["dur"]

    def test_spans_are_absolute_perf_counter_times_around_the_block(self):
        t = tracing.Tracer()
        assert t.t0 <= time.perf_counter()
        before = time.perf_counter()
        with t.span("a", step=7):
            inside = time.perf_counter()
        after = time.perf_counter()
        t.instant("not_a_span")
        with t.span("b"):
            pass
        (name, t0, t1, cpu_s, tid, args), = t.spans("a")
        assert name == "a" and tid == threading.get_ident()
        # events keep nanoseconds: allow for the rounding
        assert before - 1e-6 <= t0 <= inside <= t1 <= after + 1e-6
        assert 0 <= cpu_s <= t1 - t0 + 1e-6
        assert args["step"] == 7 and args["cpu_us"] == pytest.approx(
            cpu_s * 1e6, abs=1e-3)
        assert [s[0] for s in t.spans()] == ["a", "b"]

    def test_begin_end_is_the_span_with_args_known_at_the_end(self):
        t = tracing.Tracer()
        with t.span("outer") as outer_id:
            token = t.begin("fetch", depth_before=2)
            assert t.current_span_id() == token[0]
            t.end(token, depth=0)
            assert t.current_span_id() == outer_id
        fetch = t.events[0]
        assert fetch["name"] == "fetch"
        assert fetch["args"]["parent_id"] == outer_id
        assert fetch["args"]["depth_before"] == 2
        assert fetch["args"]["depth"] == 0
        assert t.latest_open_span_id() is None


class TestCaptureSwitch:
    """With no tracer installed, spans record for as long as a
    ``jax.profiler`` capture runs in the process."""

    def test_capture_active_follows_start_and_stop_from_any_thread(
            self, capture):
        import jax._src.profiler as jax_profiler

        # the one private attribute the switch reads: if jax moves it,
        # this is the line that says so
        assert hasattr(jax_profiler._profile_state, "profile_session")
        assert tracing.capture_active() is False
        capture.start()
        assert tracing.capture_active() is True
        seen = []
        th = threading.Thread(
            target=lambda: seen.append(tracing.capture_active()))
        th.start()
        th.join(timeout=10)
        assert seen == [True]
        capture.stop()
        assert tracing.capture_active() is False

    def test_spans_record_only_while_a_capture_runs(self, capture):
        with tracing.span("before"):
            pass
        assert tracing.get() is None and tracing.last_capture() is None
        capture.start()
        with tracing.span("during", step=1) as sid:
            assert sid is not None
            assert tracing.current_span_id() == sid
        with stepstats.timed_span("timed", "step.time_s"):
            pass
        assert stepstats.timed_fetch(iter([5]), "data_wait", None) == 5
        ring = tracing.last_capture()
        assert tracing.get() is ring
        assert isinstance(ring, tracing.RingTracer)
        assert ring.capacity == tracing.CAPTURE_CAPACITY == 16_384
        capture.stop()
        with tracing.span("after"):
            pass
        assert tracing.get() is None
        # kept after the capture so that it can be read
        assert tracing.last_capture() is ring
        assert [s[0] for s in ring.spans()] == ["during", "timed",
                                                "data_wait"]

    def test_a_second_capture_gets_a_fresh_ring(self, capture):
        capture.start()
        with tracing.span("first"):
            pass
        first = tracing.last_capture()
        capture.stop()
        # no span site runs between the two captures
        capture.start()
        with tracing.span("second"):
            pass
        capture.stop()
        second = tracing.last_capture()
        assert second is not first
        assert [s[0] for s in first.spans()] == ["first"]
        assert [s[0] for s in second.spans()] == ["second"]

    def test_the_ring_is_there_before_the_session_is_known(
            self, capture, monkeypatch):
        # threads on the lock-free path test the session and then take
        # the ring: whoever sees the new session must find ITS ring
        seen = []

        class Watched(tracing.RingTracer):
            def __init__(self, capacity):
                seen.append(tracing._capture_session
                            is tracing._profile_session())
                super().__init__(capacity)

        monkeypatch.setattr(tracing, "RingTracer", Watched)
        for _ in range(2):
            capture.start()
            assert tracing.get() is tracing.last_capture()
            assert tracing._capture_session is not None
            capture.stop()
        assert tracing.get() is None
        # each made while its capture's session was not the known one yet
        assert seen == [False, False]

    def test_an_installed_tracer_takes_the_spans_of_a_capture(self, capture):
        t = tracing.install()
        capture.start()
        with tracing.span("mine"):
            pass
        capture.stop()
        assert [e["name"] for e in t.events] == ["mine"]
        assert tracing.last_capture() is None

    def test_off_path_is_the_shared_null_context(self, capture):
        assert tracing.span("x") is tracing.span("y", step=1)


class TestCollectorPauses:
    """The garbage collector's pauses are recorded from
    ``watch_collector()`` on, tracer or not, on the spans' clock."""

    def test_watching_twice_leaves_one_callback(self):
        tracing.watch_collector()
        tracing.watch_collector()
        assert gc.callbacks.count(tracing._on_collection) == 1

    def test_initialize_is_where_it_is_switched_on(self, monkeypatch):
        from tpu_syncbn import runtime
        from tpu_syncbn.runtime import distributed

        monkeypatch.setattr(gc, "callbacks", [])
        monkeypatch.setattr(distributed, "_initialized", False)
        runtime.initialize()
        assert gc.callbacks == [tracing._on_collection]

    def test_a_full_collection_is_recorded_between_two_clock_reads(self):
        tracing.watch_collector()
        before = time.perf_counter()
        gc.collect()
        after = time.perf_counter()
        t0, t1, generation, collected = tracing.collector_pauses()[-1]
        assert generation == 2 and collected >= 0
        assert before <= t0 <= t1 <= after
        assert tracing.collector_pauses(since=after) == []
        assert tracing.collector_pauses(since=before)[-1][0] == t0

    def test_the_ring_is_bounded(self):
        tracing.watch_collector()
        assert tracing.PAUSE_CAPACITY == 8192
        for _ in range(tracing.PAUSE_CAPACITY + 3):
            gc.collect(0)
        pauses = tracing.collector_pauses()
        assert len(pauses) == tracing.PAUSE_CAPACITY
        assert [p[0] for p in pauses] == sorted(p[0] for p in pauses)

    def test_collections_switch_no_tracer_on(self, capture):
        tracing.watch_collector()
        gc.collect()
        assert tracing.get() is None and tracing.last_capture() is None

    def test_a_saved_trace_holds_them_on_a_track_of_their_own(
            self, tmp_path):
        tracing.watch_collector()
        t = tracing.Tracer()
        with t.span("train_step"):
            gc.collect()
        events = tracing.validate_trace(tracing.load_trace(
            t.save(str(tmp_path / "trace.json"))))
        step, = [e for e in events if e["name"] == "train_step"]
        pauses = [e for e in events if e["name"] == "gc"]
        full = [e for e in pauses if e["args"]["generation"] == 2]
        assert len(full) == 1 and set(full[0]["args"]) == {"generation",
                                                           "collected"}
        # the collection stands over the span it stopped, on another track
        assert step["ts"] <= full[0]["ts"]
        assert full[0]["ts"] + full[0]["dur"] <= step["ts"] + step["dur"]
        assert all(e["ph"] == "X" and e["cat"] == "tpu_syncbn"
                   and e["tid"] == tracing.COLLECTOR_TID != step["tid"]
                   for e in pauses)
        track, = [e for e in events if e["ph"] == "M"
                  and e["name"] == "thread_name"]
        assert track["tid"] == tracing.COLLECTOR_TID
        assert track["args"] == {"name": "collector"}
        # the file alone: the span sites' record is as it was
        assert [s[0] for s in t.spans()] == ["train_step"]
        assert [e["name"] for e in t.recent_events()] == ["train_step"]
        assert [e["name"] for e in t.events] == ["train_step"]

    def test_a_pause_before_the_tracer_is_not_in_its_file(self, tmp_path):
        tracing.watch_collector()
        gc.collect()
        before = len(tracing.collector_pauses())
        t = tracing.RingTracer(8)
        with t.span("h2d"):
            pass
        assert before and len(tracing.collector_pauses(t.t0)) == 0
        events = tracing.load_trace(t.save(str(tmp_path / "trace.json")))
        # no pause, no track
        assert [e["name"] for e in events if e["ph"] != "M"
                or e["name"] == "thread_name"] == ["h2d"]


class TestLoaderSpans:
    """The loader names its own work (docs/OBSERVABILITY.md, span
    table)."""

    class DS:
        def __len__(self):
            return 24

        def __getitem__(self, i):
            return np.full((4,), i, np.float32)

    def batches(self, **kw):
        from tpu_syncbn.data import DataLoader, device_prefetch

        loader = DataLoader(self.DS(), batch_size=4, **kw)
        return [np.asarray(b) for b in device_prefetch(iter(loader))]

    @pytest.mark.parametrize("num_workers", [0, 3])
    def test_a_capture_sees_one_build_per_batch_and_changes_no_batch(
            self, capture, num_workers):
        plain = self.batches(num_workers=num_workers)
        assert tracing.last_capture() is None
        capture.start()
        traced = self.batches(num_workers=num_workers)
        capture.stop()
        assert len(traced) == len(plain) == 6
        for a, b in zip(plain, traced):
            np.testing.assert_array_equal(a, b)

        ring = tracing.last_capture()
        builds = ring.spans("loader.build")
        assert sorted(b[5]["seq"] for b in builds) == list(range(6))
        workers = max(num_workers, 1)
        assert all(b[5]["worker"] == b[5]["seq"] % workers for b in builds)
        collates = {c[5]["parent_id"]: c
                    for c in ring.spans("loader.collate")}
        assert len(collates) == 6  # one a build, and no other child
        assert {e["name"] for e in ring.recent_events()} <= {
            "loader.build", "loader.collate", "loader.fetch", "data_wait",
            "h2d"}
        for b in builds:
            collate = collates[b[5]["span_id"]]
            assert collate[4] == b[4]  # on the building thread
            # the samples come first: what of the build is not collation
            assert b[1] <= collate[1] <= collate[2] <= b[2]
        # every fetch that found the stream over closed its span too
        assert len(ring.spans("data_wait")) > 6
        h2d = ring.spans("h2d")
        assert [s[5]["bytes"] for s in h2d] == [4 * 4 * 4] * 6
        assert all("parent_id" not in s[5] for s in h2d)
        if num_workers:
            waits = {s[5]["span_id"] for s in ring.spans("data_wait")}
            fetches = [f for f in ring.spans("loader.fetch")
                       if "seq" in f[5]]
            assert [f[5]["seq"] for f in fetches] == list(range(6))
            for f in fetches:
                assert f[5]["worker"] == f[5]["seq"] % num_workers
                assert f[5]["parent_id"] in waits
                assert f[5]["depth_before"] >= 0 and f[5]["depth"] >= 0
                assert f[4] == threading.get_ident()
            assert all("parent_id" not in b[5] for b in builds)

    def test_the_fetch_span_and_the_gauge_share_one_depth_sample(
            self, monkeypatch):
        from tpu_syncbn.data import DataLoader, loader as loader_mod

        reads = []
        real = loader_mod._queue_depth

        def counted(queues):
            reads.append(1)
            return real(queues)

        monkeypatch.setattr(loader_mod, "_queue_depth", counted)
        assert len(list(DataLoader(self.DS(), 4, num_workers=2))) == 6
        assert reads == []  # everything off: the depth is never read
        telemetry.set_enabled(True)
        t = tracing.install()
        assert len(list(DataLoader(self.DS(), 4, num_workers=2))) == 6
        # per batch: one read when the wait begins (the span's alone) and
        # one when it ends (the span's and the gauge's); the fetch that
        # finds the epoch over reads once
        assert len(reads) == 2 * 6 + 1
        fetches = [s for s in t.spans("loader.fetch") if "seq" in s[5]]
        assert telemetry.snapshot()["gauges"]["loader.queue_depth"] == \
            fetches[-1][5]["depth"]


class TestTrainerSpansAndScopes:
    def _dp(self):
        return parallel.DataParallel(
            tnn.convert_sync_batchnorm(_Net(nnx.Rngs(0))),
            optax.sgd(0.1), _loss,
        )

    def test_train_step_span_counts_the_calls(self):
        dp = self._dp()
        batch = jnp.ones((16, 8), jnp.float32)
        dp.train_step(batch)  # tracing off: nothing recorded, still counted
        t = tracing.install()
        dp.train_step(batch)
        dp.train_steps(batch, 2)
        steps = t.spans("train_step")
        assert [s[5]["step"] for s in steps] == [2, 3]
        assert "n_steps" not in steps[0][5] and steps[1][5]["n_steps"] == 2
        assert all("parent_id" not in s[5] for s in steps)

    def test_the_lowered_step_carries_the_scope_names(self):
        dp = self._dp()
        text = dp.lowered_train_step(
            jnp.ones((16, 8), jnp.float32)).as_text(debug_info=True)
        for scope in ("forward_backward/", "grad_allreduce/", "optimizer/",
                      "monitors/", "jvp(syncbn)/stats/", "jvp(syncbn)/psum/",
                      "jvp(syncbn)/normalize/",
                      # the backward pass keeps the forward's names
                      "forward_backward/transpose(jvp(syncbn))/normalize/"):
            assert scope in text, scope


# ---------------------------------------------------------- export/merge


class TestRank0Merge:
    def test_merge_two_hosts(self, tmp_path):
        r0, r1 = telemetry.Registry(), telemetry.Registry()
        r0.counter("steps").inc(10)
        r1.counter("steps").inc(12)
        r0.histogram("step.time_s").observe(0.01)
        r1.histogram("step.time_s").observe(3.0)
        r0.gauge("queue_depth").set(1)
        r1.gauge("queue_depth").set(7)
        p0 = str(tmp_path / "host0.jsonl")
        p1 = str(tmp_path / "host1.jsonl")
        r0.export_jsonl(p0, host=0)
        r1.export_jsonl(p1, host=1)
        merged = telemetry.merge_exports([p0, p1])
        assert merged["hosts"] == [0, 1]
        assert merged["counters"]["steps"] == 22
        h = merged["histograms"]["step.time_s"]
        assert h["count"] == 2 and sum(h["counts"]) == 2
        assert h["min"] == 0.01 and h["max"] == 3.0
        assert merged["gauges"]["queue_depth"] == 7  # last write wins
        # and the written summary round-trips
        out = str(tmp_path / "summary.json")
        summary = telemetry.write_merged_summary([p0, p1], out)
        assert json.loads(open(out).read()) == summary

    def test_bucket_drift_refuses_merge(self, tmp_path):
        r0, r1 = telemetry.Registry(), telemetry.Registry()
        r0.histogram("h", buckets=(1.0, 2.0)).observe(1.0)
        r1.histogram("h", buckets=(1.0, 5.0)).observe(1.0)
        p0 = str(tmp_path / "a.jsonl")
        p1 = str(tmp_path / "b.jsonl")
        r0.export_jsonl(p0, host=0)
        r1.export_jsonl(p1, host=1)
        with pytest.raises(ValueError, match="bucket"):
            telemetry.merge_exports([p0, p1])

    def test_merge_two_hosts_labeled(self, tmp_path):
        """ISSUE 18: labeled series are ordinary registry names
        (``family{k="v"}``), so the rank-0 export/merge path sums them
        PER SERIES — tenant a's counts never bleed into tenant b's."""
        r0, r1 = telemetry.Registry(), telemetry.Registry()
        for r, a, b in ((r0, 10, 1), (r1, 12, 2)):
            r.counter("serve.requests", labels={"tenant": "a"}).inc(a)
            r.counter("serve.requests", labels={"tenant": "b"}).inc(b)
            r.histogram("serve.latency_s",
                        labels={"tenant": "a"}).observe(a / 10)
        p0 = str(tmp_path / "host0.jsonl")
        p1 = str(tmp_path / "host1.jsonl")
        r0.export_jsonl(p0, host=0)
        r1.export_jsonl(p1, host=1)
        merged = telemetry.merge_exports([p0, p1])
        assert merged["counters"]['serve.requests{tenant="a"}'] == 22
        assert merged["counters"]['serve.requests{tenant="b"}'] == 3
        h = merged["histograms"]['serve.latency_s{tenant="a"}']
        assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 1.2
        telemetry.validate_snapshot(merged)


# ----------------------------------------------------------- labeled series


class TestLabeledMetrics:
    """ISSUE 18 tentpole: bounded-cardinality label sets on the same
    instruments, encoded into registry names — the exporters, mergers,
    and windowing above work on labeled series unchanged."""

    def test_labeled_name_roundtrip_and_sorting(self):
        n = telemetry.labeled_name("serve.requests",
                                   {"tenant": "a", "model": "m1"})
        assert n == 'serve.requests{model="m1",tenant="a"}'  # keys sorted
        assert telemetry.split_labels(n) == (
            "serve.requests", {"tenant": "a", "model": "m1"})
        # plain names pass through: no selector, not an empty one
        assert telemetry.split_labels("serve.requests") == (
            "serve.requests", None)
        assert telemetry.labeled_name("serve.requests", None) == \
            "serve.requests"

    def test_label_value_escaping_roundtrip(self):
        raw = 'we"ird\\x\nnl'
        n = telemetry.labeled_name("f.g", {"tenant": raw})
        assert telemetry.split_labels(n)[1] == {"tenant": raw}

    def test_bad_label_keys_and_family_rejected(self):
        with pytest.raises(ValueError, match="label key"):
            telemetry.labeled_name("f.g", {"Tenant": "a"})
        with pytest.raises(ValueError, match="label key"):
            telemetry.labeled_name("f.g", {"9oops": "a"})
        with pytest.raises(ValueError):
            telemetry.labeled_name('f.g{already="labeled"}', {"tenant": "a"})

    def test_labeled_ops_create_distinct_series(self):
        telemetry.set_enabled(True)
        telemetry.count("serve.requests", 2)
        telemetry.count("serve.requests", 5, labels={"tenant": "a"})
        telemetry.count("serve.requests", 7, labels={"tenant": "b"})
        telemetry.set_gauge("serve.queue_depth", 3, labels={"tenant": "a"})
        telemetry.observe("serve.latency_s", 0.2, labels={"tenant": "a"})
        snap = telemetry.snapshot()
        assert snap["counters"]["serve.requests"] == 2
        assert snap["counters"]['serve.requests{tenant="a"}'] == 5
        assert snap["counters"]['serve.requests{tenant="b"}'] == 7
        assert snap["gauges"]['serve.queue_depth{tenant="a"}'] == 3
        assert snap["histograms"]['serve.latency_s{tenant="a"}']["count"] == 1
        telemetry.validate_snapshot(snap)

    def test_labels_match_selector_semantics(self):
        assert telemetry.labels_match({"tenant": "a", "model": "m"},
                                      {"tenant": "a"})
        assert not telemetry.labels_match({"tenant": "b"}, {"tenant": "a"})
        # a plain (unlabeled) series never matches a selector; the
        # empty selector matches every LABELED series
        assert not telemetry.labels_match(None, {"tenant": "a"})
        assert not telemetry.labels_match(None, {})
        assert telemetry.labels_match({"tenant": "b"}, {})

    def test_cardinality_cap_overflows_into_other(self):
        """Past the per-family cap, new combinations collapse
        deterministically into the ``other`` series and each routed
        call bumps ``telemetry.cardinality_dropped`` — an unbounded
        label can cost at most cap+1 series, never registry blowup."""
        telemetry.set_enabled(True)
        r = telemetry.REGISTRY
        r.set_label_cardinality("serve.requests", 2)
        for i in range(10):
            telemetry.count("serve.requests", 1,
                            labels={"tenant": f"t{i}"})
        snap = telemetry.snapshot()
        # first-come-first-kept: t0, t1 admitted, the rest collapsed
        assert snap["counters"]['serve.requests{tenant="t0"}'] == 1
        assert snap["counters"]['serve.requests{tenant="t1"}'] == 1
        assert snap["counters"]['serve.requests{tenant="other"}'] == 8
        assert snap["counters"]["telemetry.cardinality_dropped"] == 8
        assert not any('tenant="t5"' in k for k in snap["counters"])
        # admitted combinations keep routing to their own series
        telemetry.count("serve.requests", 1, labels={"tenant": "t1"})
        assert telemetry.snapshot()["counters"][
            'serve.requests{tenant="t1"}'] == 2

    def test_cap_is_per_family(self):
        telemetry.set_enabled(True)
        telemetry.REGISTRY.set_label_cardinality("f.a", 1)
        telemetry.count("f.a", labels={"tenant": "x"})
        telemetry.count("f.a", labels={"tenant": "y"})  # over f.a's cap
        telemetry.count("f.b", labels={"tenant": "y"})  # f.b unaffected
        snap = telemetry.snapshot()
        assert snap["counters"]['f.a{tenant="other"}'] == 1
        assert snap["counters"]['f.b{tenant="y"}'] == 1

    def test_disabled_path_ignores_labels(self, monkeypatch):
        monkeypatch.delenv("TPU_SYNCBN_TELEMETRY", raising=False)
        telemetry.set_enabled(None)  # env default: off
        telemetry.count("serve.requests", labels={"tenant": "a"})
        telemetry.set_gauge("serve.queue_depth", 1, labels={"tenant": "a"})
        telemetry.observe("serve.latency_s", 0.1, labels={"tenant": "a"})
        assert len(telemetry.REGISTRY) == 0

    def test_deprecated_flat_mirror_warns_once(self):
        telemetry.reset_deprecated_warnings()
        with pytest.warns(DeprecationWarning, match="deprecated flat"):
            telemetry.warn_deprecated_name(
                "serve.version.active",
                'serve.version{mode="active"}')
        # once per process per old name: a second call is silent
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("error")
            telemetry.warn_deprecated_name(
                "serve.version.active",
                'serve.version{mode="active"}')
        telemetry.reset_deprecated_warnings()


# ------------------------------------------------------------- stepstats


class TestStepstatsHost:
    def test_timed_span_records_both(self):
        telemetry.set_enabled(True)
        t = tracing.install()
        with stepstats.timed_span("step", "step.time_s"):
            pass
        assert telemetry.snapshot()["histograms"]["step.time_s"]["count"] == 1
        assert t.events[0]["name"] == "step"

    def test_instrumented_batches_passthrough(self):
        telemetry.set_enabled(True)
        out = list(stepstats.instrumented_batches(iter([1, 2, 3])))
        assert out == [1, 2, 3]
        h = telemetry.snapshot()["histograms"]["step.data_wait_s"]
        assert h["count"] == 3

    def test_zero_cost_when_all_off(self):
        telemetry.set_enabled(False)
        with stepstats.timed_span("step", "step.time_s"):
            pass
        assert len(telemetry.REGISTRY) == 0

    def test_device_prefetch_excludes_terminal_fetch(self):
        # the end-of-epoch StopIteration wait must not be a data-wait
        # sample (it would add one outlier per epoch)
        from tpu_syncbn.data import device_prefetch

        telemetry.set_enabled(True)
        batches = [np.ones((4,), np.float32)] * 3
        out = list(device_prefetch(iter(batches)))
        assert len(out) == 3
        snap = telemetry.snapshot()
        assert snap["histograms"]["loader.data_wait_s"]["count"] == 3
        assert snap["histograms"]["loader.h2d_s"]["count"] == 3


class _Net(nnx.Module):
    def __init__(self, rngs):
        self.fc = nnx.Linear(8, 8, rngs=rngs)
        self.bn = tnn.BatchNorm1d(8)

    def __call__(self, x):
        return self.bn(self.fc(x))


def _loss(m, b):
    return (m(b) ** 2).mean()


class TestOnDeviceMonitors:
    """The StepOutput.monitors contract: health scalars computed inside
    the compiled step (no extra host syncs — they are ordinary async
    step outputs)."""

    def _dp(self, **kw):
        return parallel.DataParallel(
            tnn.convert_sync_batchnorm(_Net(nnx.Rngs(0))),
            optax.sgd(0.1), _loss, **kw,
        )

    def test_monitor_keys_and_values(self):
        out = self._dp().train_step(jnp.ones((16, 8), jnp.float32))
        mon = {k: float(v) for k, v in out.monitors.items()}
        assert {"grad_norm", "grad_nonfinite", "state_nonfinite",
                "bn_mean_max_abs", "bn_var_max", "bn_var_min",
                "bn_layers"} <= set(mon)
        assert mon["grad_norm"] >= 0 and np.isfinite(mon["grad_norm"])
        assert mon["grad_nonfinite"] == 0
        assert mon["state_nonfinite"] == 0
        assert mon["bn_layers"] == 1
        assert mon["bn_var_max"] >= mon["bn_var_min"] > 0

    def test_full_mode_emits_per_layer_keys(self):
        out = self._dp(monitors="full").train_step(
            jnp.ones((16, 8), jnp.float32)
        )
        assert any(k.startswith("bn_var_min.") for k in out.monitors)

    def test_monitors_off_is_empty(self):
        out = self._dp(monitors=False).train_step(
            jnp.ones((16, 8), jnp.float32)
        )
        assert out.monitors == {}

    def test_zero_mode_grad_norm_matches_replicated(self):
        x = jnp.linspace(-1, 1, 16 * 8).reshape(16, 8).astype(jnp.float32)
        plain = self._dp().train_step(x)
        zero = self._dp(zero=True).train_step(x)
        np.testing.assert_allclose(
            float(zero.monitors["grad_norm"]),
            float(plain.monitors["grad_norm"]), rtol=1e-4,
        )

    def test_nonfinite_batch_is_counted(self):
        dp = self._dp(divergence_guard="skip_step")
        x = jnp.full((16, 8), jnp.nan, jnp.float32)
        out = dp.train_step(x)
        assert float(out.monitors["grad_nonfinite"]) > 0
        assert float(out.metrics["nonfinite"]) == 1.0

    def test_invalid_monitors_value_rejected(self):
        with pytest.raises(ValueError, match="monitors"):
            self._dp(monitors="everything")

    def test_gan_trainer_rejects_bad_monitors_value(self):
        # GANTrainer shares DataParallel's monitors contract — unknown
        # values must raise, not silently coerce to bool
        with pytest.raises(ValueError, match="monitors"):
            parallel.GANTrainer(
                _Net(nnx.Rngs(0)), _Net(nnx.Rngs(1)),
                optax.sgd(0.1), optax.sgd(0.1), monitors="everything",
            )


class TestStateHealthUnit:
    def test_classifies_running_stats_by_path(self):
        state = {
            "bn": {"running_mean": jnp.array([0.5, -2.0]),
                   "running_var": jnp.array([0.1, 4.0]),
                   "num_batches_tracked": jnp.array(3, jnp.int32)},
            "other": jnp.array([jnp.inf]),
        }
        h = {k: float(v) for k, v in stepstats.state_health(state).items()}
        assert h["bn_mean_max_abs"] == 2.0
        assert h["bn_var_max"] == 4.0 and h["bn_var_min"] == pytest.approx(0.1)
        assert h["bn_layers"] == 1
        assert h["state_nonfinite"] == 1  # the inf in "other"

    def test_no_bn_state_reports_vacuous_defaults(self):
        h = {k: float(v)
             for k, v in stepstats.state_health({"w": jnp.ones(3)}).items()}
        assert h["bn_layers"] == 0
        assert h["bn_var_max"] == 0 and h["bn_mean_max_abs"] == 0


# ------------------------------------------------ correlation / wiring


class TestSpanCorrelation:
    def test_watchdog_stall_dump_carries_span_id(self, caplog):
        from tpu_syncbn.runtime import resilience

        telemetry.set_enabled(True)
        t = tracing.install()
        with t.span("step") as sid:
            with resilience.Watchdog(0.05, name="corr-test",
                                     poll_s=0.01) as wd:
                deadline = time.monotonic() + 5
                while wd.stall_count == 0 and time.monotonic() < deadline:
                    time.sleep(0.01)
        assert wd.stall_count >= 1
        counters = telemetry.snapshot()["counters"]
        assert counters["resilience.watchdog_stalls"] >= 1
        marks = [e for e in t.events if e["name"] == "watchdog_stall"]
        assert marks and marks[0]["args"]["span_id"] == sid

    def test_resilient_loop_counters_share_export_path(self, tmp_path):
        from tpu_syncbn.runtime import resilience

        telemetry.set_enabled(True)
        dp = parallel.DataParallel(
            tnn.convert_sync_batchnorm(_Net(nnx.Rngs(0))),
            optax.sgd(0.1), _loss,
        )
        loop = resilience.ResilientLoop(dp, str(tmp_path), ckpt_every=2)
        batches = [jnp.ones((16, 8), jnp.float32)] * 4
        summary = loop.run(batches)
        assert summary["steps"] == 4 and summary["checkpoints"] == 2
        snap = telemetry.snapshot()
        # the loop's CounterGroup mirrored into the registry...
        assert snap["counters"]["resilience.checkpoints"] == 2
        # ...and its step loop fed the step/data-wait histograms
        assert snap["histograms"]["step.time_s"]["count"] == 4
        assert snap["histograms"]["checkpoint.save_s"]["count"] == 2

    def test_checkpoint_timings_recorded(self, tmp_path):
        from tpu_syncbn.utils import checkpoint as ckpt

        telemetry.set_enabled(True)
        t = tracing.install()
        tree = {"w": np.arange(8, dtype=np.float32)}
        ckpt.save_checkpoint(str(tmp_path), 1, tree)
        ckpt.load_checkpoint(str(tmp_path), tree)
        assert ckpt.verify_checkpoint(str(tmp_path), 1)
        snap = telemetry.snapshot()
        assert snap["counters"]["checkpoint.saves"] == 1
        assert snap["counters"]["checkpoint.loads"] == 1
        assert snap["histograms"]["checkpoint.save_s"]["count"] == 1
        assert snap["histograms"]["checkpoint.load_s"]["count"] == 1
        assert snap["histograms"]["checkpoint.verify_s"]["count"] == 1
        names = {e["name"] for e in t.events}
        assert {"checkpoint_save", "checkpoint_load",
                "checkpoint_verify"} <= names
        # an async save is counted where the loop pays it and lands in
        # the same save histogram from the writer thread
        with ckpt.AsyncCheckpointer() as writer:
            writer.save(str(tmp_path), 2, tree)
            assert writer.flush(timeout=30)
        snap = telemetry.snapshot()
        assert snap["counters"]["checkpoint.async_saves"] == 1
        assert snap["histograms"]["checkpoint.save_s"]["count"] == 2

    def test_collective_tallies_count_at_trace_time(self):
        telemetry.set_enabled(True)
        dp = parallel.DataParallel(
            tnn.convert_sync_batchnorm(_Net(nnx.Rngs(0))),
            optax.sgd(0.1), _loss,
        )
        dp.train_step(jnp.ones((16, 8), jnp.float32))
        tallies = stepstats.collective_tallies()
        assert tallies.get("collectives.pmean.calls", 0) >= 1
        assert tallies.get("collectives.pmean.bytes", 0) > 0

    def test_loader_telemetry(self):
        from tpu_syncbn.data import DataLoader

        telemetry.set_enabled(True)

        class DS:
            def __len__(self):
                return 16

            def __getitem__(self, i):
                return np.full((4,), i, np.float32)

        loader = DataLoader(DS(), batch_size=4, num_workers=2)
        batches = list(loader)
        assert len(batches) == 4
        snap = telemetry.snapshot()
        assert snap["counters"]["loader.batches"] == 4
        assert snap["histograms"]["loader.fetch_wait_s"]["count"] == 4
        assert "loader.queue_depth" in snap["gauges"]
