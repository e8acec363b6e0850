"""Layer-3 audit tests: the sharding-flow pass propagates layouts the
way the programs actually shard, every detector (accidental
replication, implicit resharding, memory-bound breach) is proven live
by a planted mutation on a hand-built program — mirroring the
contract-mutation matrix in tests/test_audit_contracts.py — and the
extended CLI surface (``--shardings``, ``--mem-budget``,
``--write-goldens`` diff/refuse, ``--changed-only``, env restoration)
behaves.

Everything here traces abstractly; only the one ``--shardings``
subprocess (the ISSUE 10 acceptance pin) compiles anything.
"""

import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_syncbn.audit import contracts as contracts_mod
from tpu_syncbn.audit import jaxpr_audit, sharding_audit
from tpu_syncbn.audit.contracts import (
    ShardingContract,
    compare_contracts,
    compare_sharding,
    extract_contract,
)
from tpu_syncbn.compat import shard_map
from tpu_syncbn.mesh_axes import DATA_AXIS

pytestmark = pytest.mark.audit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_DIR = os.path.join(ROOT, "tests", "contracts")


def _mesh():
    return Mesh(np.array(jax.devices()), (DATA_AXIS,))


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.fixture(scope="module")
def live():
    """All registered programs, traced once (shared with the layer-1
    suite's registry — the builders are the expensive part)."""
    return jaxpr_audit.build_contracts()


class TestPropagation:
    """Ground truth for the abstract domains on hand-built programs."""

    def test_psum_ends_replicated_reduce_scatter_does_not(self):
        mesh = _mesh()

        def body(x):
            s = jax.lax.psum(x, DATA_AXIS)          # -> replicated
            r = jax.lax.psum_scatter(
                s, DATA_AXIS, scatter_dimension=0, tiled=True
            )                                        # -> varying again
            return s, r

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=(P(), P(DATA_AXIS)),
        ))
        flow = sharding_audit.analyze_program(
            fn, (_sds(64, 4),), mesh=mesh, in_specs=(P(DATA_AXIS),),
        )
        assert flow.collectives_explained == 2
        assert flow.implicit_reshards == 0
        assert flow.out_spec_strs() == sorted(["P()", "P('data')"])

    def test_per_device_bytes_respect_the_sharding_factor(self):
        # a P('data') 16x4 f32 input is 256 B global, 32 B per device
        mesh = _mesh()
        fn = jax.jit(shard_map(
            lambda x: x * 2, mesh=mesh,
            in_specs=(P(DATA_AXIS),), out_specs=P(DATA_AXIS),
        ))
        flow = sharding_audit.analyze_program(
            fn, (_sds(16, 4),), mesh=mesh, in_specs=(P(DATA_AXIS),),
        )
        # input + doubled output live simultaneously: 2 shards = 64 B
        assert flow.peak_bytes_per_device == 64

    def test_scan_carry_fixpoint_converges_to_varying(self):
        # carry starts as a replicated zeros() but mixes with a varying
        # input inside the body — the fixpoint must settle on varying
        # and the final output (after psum) back on replicated
        mesh = _mesh()

        def body(x):
            def step(carry, sl):
                return carry + sl, ()

            acc, _ = jax.lax.scan(
                step, jnp.zeros(x.shape[1:], x.dtype), x
            )
            return jax.lax.psum(acc, DATA_AXIS)

        # check_vma=False: jax's own checker refuses an un-cast
        # replicated carry that turns varying — the analyzer's fixpoint
        # is what is under test, so the program skips jax's
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, DATA_AXIS),),
            out_specs=P(), check_vma=False,
        ))
        flow = sharding_audit.analyze_program(
            fn, (_sds(4, 16),), mesh=mesh, in_specs=(P(None, DATA_AXIS),),
        )
        assert flow.implicit_reshards == 0
        assert flow.out_spec_strs() == ["P()"]

    def test_long_carry_chain_converges_past_the_axis_count(self):
        """Review finding: the fixpoint bound must scale with the carry
        CHAIN length, not the mesh-axis count — a varying value takes
        one iteration per link to propagate through c2'=c1, c3'=c2, …
        A stale (over-replicated) tail carry would show up here as a
        scan output flagged fully-replicated."""
        mesh = _mesh()

        def body(x):
            def step(carry, sl):
                c1, c2, c3, c4 = carry
                return (sl, c1, c2, c3), ()

            init = tuple(
                jnp.zeros(x.shape[1:], x.dtype) for _ in range(4)
            )
            carry, _ = jax.lax.scan(step, init, x)
            return carry[3]  # varying only after 4 propagation steps

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(None, DATA_AXIS),),
            out_specs=P(DATA_AXIS), check_vma=False,  # as above
        ))
        flow = sharding_audit.analyze_program(
            fn, (_sds(4, 64),), mesh=mesh,
            in_specs=(P(None, DATA_AXIS),),
            replication_threshold=1,  # ANY stale claim would be flagged
        )
        # the init zeros are legitimately replicated; the scan's carry
        # outputs must NOT be (they went varying through the chain)
        assert not any("scan" in d for d in flow.replication_detail), \
            flow.replication_detail

    def test_vmap_named_axis_does_not_pollute_the_mesh_lattice(self):
        """Review finding: a vmap-minted named axis on psum is
        intra-device — it must neither count as an explained mesh
        collective nor hide genuine full replication behind a non-mesh
        axis name in the replicated set."""
        mesh = _mesh()

        def body(x):
            per_row = jax.vmap(
                lambda r: jax.lax.psum(r, "batch"), axis_name="batch"
            )(x)
            big = jax.lax.all_gather(
                per_row, DATA_AXIS, axis=0, tiled=True
            )  # genuinely replicated over the whole mesh
            return jax.lax.psum_scatter(
                big, DATA_AXIS, scatter_dimension=0, tiled=True
            )

        # check_vma=False: jax 0.9's checker asserts on a vmap-axis
        # psum of a mesh-varying value; the analyzer must not care
        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=P(DATA_AXIS), check_vma=False,
        ))
        flow = sharding_audit.analyze_program(
            fn, (_sds(64, 8),), mesh=mesh, in_specs=(P(DATA_AXIS),),
            replication_threshold=512,
        )
        # only the two MESH collectives are explained; the vmap psum
        # is a pure per-device op
        assert flow.collectives_explained == 2
        # the gather's full-mesh replication is still detected even
        # with the vmap axis in play
        assert flow.replicated_intermediates >= 1
        assert any("all_gather" in d for d in flow.replication_detail)

    def test_broadcast_spec_expands_prefix_trees(self):
        arg = {"a": np.zeros((2,)), "b": (np.zeros((2,)), np.zeros((2,)))}
        flat = sharding_audit.broadcast_spec(P(DATA_AXIS), arg)
        assert flat == [P(DATA_AXIS)] * 3
        mixed = sharding_audit.broadcast_spec(
            {"a": P(), "b": P(DATA_AXIS)}, arg
        )
        assert mixed == [P(), P(DATA_AXIS), P(DATA_AXIS)]
        with pytest.raises(ValueError, match="keys"):
            sharding_audit.broadcast_spec({"a": P()}, arg)

    def test_spec_strings_are_canonical(self):
        assert sharding_audit.spec_leaf_str(P()) == "P()"
        assert sharding_audit.spec_leaf_str(P("data", None)) == "P('data')"
        assert sharding_audit.spec_leaf_str(P(None, "data")) \
            == "P(None, 'data')"
        assert sharding_audit.spec_leaf_str(P(("data", "fsdp"))) \
            == "P(('data', 'fsdp'))"


class TestPlantedReplication:
    """Detector (a): an intermediate materialized fully replicated on
    every device above the byte threshold is caught."""

    def _gather_program(self):
        mesh = _mesh()

        def body(x):
            g = jax.lax.all_gather(x, DATA_AXIS, axis=0, tiled=True)
            # the gathered (full, replicated) array outlives its use
            return jax.lax.psum_scatter(
                g * 2.0, DATA_AXIS, scatter_dimension=0, tiled=True
            )

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(DATA_AXIS),),
            out_specs=P(DATA_AXIS),
        ))
        return fn, mesh

    def test_forced_replication_is_caught(self):
        fn, mesh = self._gather_program()
        c = extract_contract(
            fn, (_sds(64, 4),), name="planted.replication", world=8,
            arg_labels=("x",), mesh=mesh, in_specs=(P(DATA_AXIS),),
            replication_threshold=512,  # the gather is 1 KiB/device
        )
        s = c.sharding
        assert s.replicated_intermediates >= 1
        assert s.max_replicated_bytes == 64 * 4 * 4
        assert any("all_gather" in d for d in s.replication_detail)
        vs = jaxpr_audit.check_sharding({"planted.replication": c})
        assert "sharding.replication" in {v.rule for v in vs}
        assert any("fully replicated" in v.message for v in vs)

    def test_same_program_below_threshold_is_quiet(self):
        fn, mesh = self._gather_program()
        c = extract_contract(
            fn, (_sds(64, 4),), name="planted.quiet", world=8,
            arg_labels=("x",), mesh=mesh, in_specs=(P(DATA_AXIS),),
        )  # default 1 MiB threshold
        assert c.sharding.replicated_intermediates == 0
        # ...but the biggest replicated value is still recorded for the
        # golden, so drift below the alarm bar is pinned too
        assert c.sharding.max_replicated_bytes == 64 * 4 * 4
        assert jaxpr_audit.check_sharding({"planted.quiet": c}) == []


class TestPlantedReshard:
    """Detector (b): a layout change not explained by a declared
    collective is caught."""

    def test_sharding_constraint_gather_is_caught(self):
        mesh = _mesh()

        def fn(x):
            # un-sharding a sharded value forces an all-gather no
            # collective in the program text explains
            full = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P())
            )
            return full * 2.0

        c = extract_contract(
            jax.jit(fn), (_sds(16, 4),), name="planted.reshard", world=8,
            arg_labels=("x",), mesh=mesh, in_specs=(P(DATA_AXIS),),
        )
        s = c.sharding
        assert s.implicit_reshards == 1
        assert any("sharding_constraint" in d for d in s.reshard_detail)
        vs = jaxpr_audit.check_sharding({"planted.reshard": c})
        assert [v.rule for v in vs] == ["sharding.implicit_reshard"]

    def test_replicated_to_sharded_constraint_is_free(self):
        mesh = _mesh()

        def fn(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(DATA_AXIS))
            ) * 2.0

        c = extract_contract(
            jax.jit(fn), (_sds(16, 4),), name="planted.slice", world=8,
            arg_labels=("x",), mesh=mesh, in_specs=(P(),),
        )
        assert c.sharding.implicit_reshards == 0

    def test_shard_map_entry_mismatch_is_caught(self):
        mesh = _mesh()

        def fn(x):
            # x is declared P('data') at the top but this shard_map
            # wants it replicated: jit silently gathers before entry
            inner = shard_map(
                lambda v: jax.lax.psum(v.sum(), DATA_AXIS),
                mesh=mesh, in_specs=(P(),), out_specs=P(),
            )
            return inner(x)

        flow = sharding_audit.analyze_program(
            jax.jit(fn), (_sds(16, 4),), mesh=mesh,
            in_specs=(P(DATA_AXIS),),
        )
        assert flow.implicit_reshards == 1
        assert any("shard_map" in d for d in flow.reshard_detail)

    def test_conflicting_elementwise_operands_are_caught(self):
        # a true conflict needs the SAME dim sharded on DIFFERENT axes
        # (a 2-axis mesh); cross-dim sharding differences are free
        # slicing and must stay quiet — both pinned here
        from tpu_syncbn.mesh_axes import MODEL_AXIS

        mesh2 = Mesh(
            np.array(jax.devices()).reshape(4, 2),
            (DATA_AXIS, MODEL_AXIS),
        )

        def fn(x, y):
            return x + y

        flow = sharding_audit.analyze_program(
            jax.jit(fn), (_sds(16, 16), _sds(16, 16)), mesh=mesh2,
            in_specs=(P(DATA_AXIS), P(MODEL_AXIS)),
        )
        assert flow.implicit_reshards >= 1
        assert any("'data'" in d and "'model'" in d
                   for d in flow.reshard_detail)
        # cross-dim difference: each operand slices locally, no comm
        quiet = sharding_audit.analyze_program(
            jax.jit(fn), (_sds(16, 16), _sds(16, 16)), mesh=_mesh(),
            in_specs=(P(DATA_AXIS), P(None, DATA_AXIS)),
        )
        assert quiet.implicit_reshards == 0


class TestPlantedMemoryBound:
    """Detector (c): the per-device peak-memory contract."""

    def test_inflated_peak_breaches_the_budget(self):
        mesh = _mesh()
        fn = jax.jit(shard_map(
            lambda x: x * 2, mesh=mesh,
            in_specs=(P(DATA_AXIS),), out_specs=P(DATA_AXIS),
        ))
        c = extract_contract(
            fn, (_sds(16, 4),), name="planted.mem", world=8,
            arg_labels=("x",), mesh=mesh, in_specs=(P(DATA_AXIS),),
        )
        assert c.sharding.peak_bytes_per_device == 64
        # generous budget: quiet
        assert jaxpr_audit.check_sharding(
            {"planted.mem": c}, mem_budget=1 << 20
        ) == []
        # budget below the real peak: caught
        vs = jaxpr_audit.check_sharding({"planted.mem": c}, mem_budget=32)
        assert [v.rule for v in vs] == ["sharding.mem_budget"]
        assert "exceeds" in vs[0].message

    def test_inflated_golden_peak_is_a_golden_mismatch(self, live):
        """The planted-mutation shape of the same detector: a program
        whose propagated peak drifts off its pinned value fails the
        golden comparison."""
        c = copy.deepcopy(live["dataparallel.train_step"])
        golden = copy.deepcopy(c)
        c.sharding.peak_bytes_per_device *= 10  # inflate
        diffs = compare_contracts(c, golden)
        assert any("peak_bytes_per_device" in d for d in diffs)


class TestShardingGoldens:
    """The golden comparison pins every layer-3 field."""

    def test_every_registry_program_has_a_sharding_block(self, live):
        assert len(live) >= 9  # ISSUE 10 acceptance floor
        for name, c in live.items():
            assert c.sharding is not None, name
            assert c.sharding.mesh_axes, name

    def test_pinned_goldens_carry_sharding_blocks(self, live):
        violations, unpinned = jaxpr_audit.check_goldens(live, GOLDEN_DIR)
        assert unpinned == []
        assert violations == [], [v.format() for v in violations]
        for name in live:
            golden = contracts_mod.load_contract(
                jaxpr_audit.golden_path(GOLDEN_DIR, name)
            )
            assert golden.sharding is not None, name

    def test_strategy_programs_are_pinned_ground_truth(self, live):
        """The previously-siloed strategies' first contracts: the
        module docstrings' collective claims, machine-checked."""
        tp = live["tensor.tp_mlp"]
        assert tp.collectives == {"psum": 1}
        assert tp.sharding.in_specs["w1"] == ["P(None, 'model')"]
        assert tp.sharding.in_specs["w2"] == ["P('model')"]
        moe = live["expert.switch_moe"]
        assert moe.collectives["all_to_all"] == 2
        pipe = live["pipeline.gpipe"]
        assert pipe.collectives["ppermute"] == 1  # scan body: counted once
        ring = live["sequence.ring_attention"]
        assert set(ring.collectives) == {"ppermute"}
        assert ring.sharding.out_specs == ["P(None, 'seq')"]
        # the ZeRO program's param gather is the known replication cost,
        # recorded (not flagged: below threshold on the tiny fixture)
        zg = live["dataparallel.zero_guard.train_step"]
        assert zg.sharding.max_replicated_bytes > 0
        assert zg.sharding.replicated_intermediates == 0

    def test_sharding_json_round_trip(self, live):
        for c in live.values():
            again = contracts_mod.ProgramContract.from_json(
                json.loads(json.dumps(c.to_json()))
            )
            assert compare_contracts(c, again) == []

    def test_sharding_schema_bump_refuses_stale_golden(self, live):
        blob = next(iter(live.values())).to_json()
        blob["sharding"]["schema"] = -1
        with pytest.raises(ValueError, match="re-pin"):
            contracts_mod.ProgramContract.from_json(blob)

    def test_each_sharding_field_mutation_is_caught(self, live):
        base = live["serve.eval_bucket8"]
        mutations = {
            "out_specs": lambda s: s.out_specs.append("P('model')"),
            "implicit_reshards": lambda s: setattr(
                s, "implicit_reshards", s.implicit_reshards + 1),
            "replicated_intermediates": lambda s: setattr(
                s, "replicated_intermediates", 3),
            "collectives_explained": lambda s: setattr(
                s, "collectives_explained", s.collectives_explained + 2),
            "max_replicated_bytes": lambda s: setattr(
                s, "max_replicated_bytes", s.max_replicated_bytes + 64),
            "in_specs": lambda s: s.in_specs["batch"].append("P()"),
            "mesh_axes": lambda s: s.mesh_axes.update(hijack=2),
        }
        for field, mutate in mutations.items():
            c = copy.deepcopy(base)
            mutate(c.sharding)
            diffs = compare_contracts(c, base)
            assert any(f"sharding.{field}" in d for d in diffs), (
                field, diffs
            )

    def test_missing_sharding_block_is_a_violation_both_ways(self, live):
        c = live["dataparallel.train_step"]
        stripped = copy.deepcopy(c)
        stripped.sharding = None
        # actual analyzed, golden missing the block -> re-pin demanded
        diffs = compare_contracts(c, stripped)
        assert any("golden pins none" in d for d in diffs)
        # actual NOT analyzed vs a pinned golden: equally a violation —
        # a registry edit that drops mesh/in_specs must not silently
        # disable every pinned layer-3 invariant (review finding)
        diffs = compare_contracts(stripped, c)
        assert any("registry regression" in d for d in diffs)

    def test_xla_peak_compares_with_tolerance(self, live):
        c = copy.deepcopy(live["dataparallel.train_step"])
        golden = copy.deepcopy(c)
        c.sharding.xla_peak_bytes = 10_000
        golden.sharding.xla_peak_bytes = 10_500  # within 10%
        assert compare_sharding(c.sharding, golden.sharding, "t") == []
        golden.sharding.xla_peak_bytes = 20_000  # way off
        diffs = compare_sharding(c.sharding, golden.sharding, "t")
        assert any("xla_peak_bytes" in d for d in diffs)
        golden.sharding.xla_peak_bytes = None  # not compiled: skipped
        assert compare_sharding(c.sharding, golden.sharding, "t") == []


class TestAuditCLI:
    """ISSUE 10 acceptance: `--strict --shardings` exits 0 at HEAD with
    sharding contracts golden-checked for every registered program —
    plus the new golden-workflow and fast-mode flags."""

    def test_strict_shardings_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_syncbn.audit",
             "--strict", "--shardings", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=600,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["ok"] is True
        assert report["programs_checked"] >= 9
        assert report["violations"] == [] and report["unpinned"] == []

    def test_write_goldens_prints_diff_and_refuses_without_force(
        self, tmp_path, capsys
    ):
        from tpu_syncbn.audit import __main__ as cli

        gdir = str(tmp_path / "contracts")
        # empty dir: everything is a new pin -> written, exit 0
        assert cli.main(["--write-goldens", "--contracts-dir", gdir]) == 0
        out = capsys.readouterr().out
        assert "<new golden — no previous pin>" in out
        assert "pinned" in out
        # corrupt one golden: a re-pin must show the old->new diff and
        # refuse without --force
        path = jaxpr_audit.golden_path(gdir, "tensor.tp_mlp")
        blob = json.load(open(path))
        blob["collectives"]["psum"] = 7
        json.dump(blob, open(path, "w"))
        assert cli.main(["--write-goldens", "--contracts-dir", gdir]) == 1
        out = capsys.readouterr().out
        assert "collectives[psum] = 1, golden pins 7" in out
        assert "refusing" in out and "--force" in out
        assert json.load(open(path))["collectives"]["psum"] == 7  # intact
        # --force overwrites after review
        assert cli.main(
            ["--write-goldens", "--contracts-dir", gdir, "--force"]
        ) == 0
        assert json.load(open(path))["collectives"]["psum"] == 1

    def test_repin_that_would_erase_xla_peak_is_a_reviewable_diff(
        self, live, tmp_path
    ):
        """Review finding: goldens pinned with --shardings carry the
        memory cross-check; a later plain --write-goldens must surface
        the would-be erasure as a diff (demanding --force), not drop
        the field silently."""
        gdir = str(tmp_path)
        c = copy.deepcopy(live["tensor.tp_mlp"])
        c.sharding.xla_peak_bytes = 1704  # as a --shardings pin would
        contracts_mod.save_contract(
            c, jaxpr_audit.golden_path(gdir, c.name)
        )
        plain = copy.deepcopy(c)
        plain.sharding.xla_peak_bytes = None  # memory=False re-trace
        diffs = jaxpr_audit.golden_diffs({c.name: plain}, gdir)
        assert any("erase the memory cross-check" in d
                   for d in diffs.get(c.name, [])), diffs

    def test_write_goldens_noop_when_everything_matches(
        self, tmp_path, capsys
    ):
        from tpu_syncbn.audit import __main__ as cli

        gdir = str(tmp_path / "contracts")
        assert cli.main(["--write-goldens", "--contracts-dir", gdir]) == 0
        capsys.readouterr()
        assert cli.main(["--write-goldens", "--contracts-dir", gdir]) == 0
        assert "nothing re-pinned" in capsys.readouterr().out

    def test_force_without_write_goldens_is_a_usage_error(self):
        from tpu_syncbn.audit import __main__ as cli

        assert cli.main(["--force"]) == 2

    def test_changed_only_lints_only_the_changed_files(self, capsys):
        from tpu_syncbn.audit import __main__ as cli

        # vs HEAD in this repo: a valid ref; whatever is changed must
        # still lint clean, and the run must be a subset of the package
        rc = cli.main(["--no-contracts", "--changed-only", "HEAD",
                       "--json"])
        assert rc == 0
        report = json.loads(capsys.readouterr().out)
        from tpu_syncbn.audit.srclint import package_files

        assert report["files_linted"] <= len(package_files())

    def test_changed_files_include_untracked_modules(self, tmp_path):
        """Review finding: a brand-new (untracked) package module is
        exactly the file most likely to carry a fresh violation —
        `git diff` alone misses it, so ls-files --others rides along."""
        from tpu_syncbn.audit import __main__ as cli

        pkg = tmp_path / "repo" / "pkg"
        pkg.mkdir(parents=True)
        repo = str(tmp_path / "repo")
        subprocess.run(["git", "init", "-q"], cwd=repo, check=True)
        tracked = pkg / "tracked.py"
        tracked.write_text("x = 1\n")
        subprocess.run(["git", "add", "."], cwd=repo, check=True)
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             "commit", "-qm", "seed"], cwd=repo, check=True,
        )
        tracked.write_text("x = 2\n")                 # diffed
        (pkg / "brand_new.py").write_text("y = 1\n")  # untracked
        changed = cli._changed_files("HEAD", str(pkg))
        names = {os.path.basename(p) for p in changed}
        assert names == {"tracked.py", "brand_new.py"}

    def test_mem_budget_cli_fails_a_tiny_budget(self):
        # every traced program exceeds a 1-byte budget: exit 1 with
        # sharding.mem_budget findings
        proc = subprocess.run(
            [sys.executable, "-m", "tpu_syncbn.audit", "--no-lint",
             "--mem-budget", "1", "--json"],
            capture_output=True, text=True, cwd=ROOT, timeout=600,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)
        assert report["rule_counts"].get("sharding.mem_budget", 0) \
            == report["programs_checked"]

    def test_env_forcing_is_restored_after_main(self, monkeypatch):
        """ISSUE 10 satellite: the CLI's pinned-mesh env mutation is
        snapshotted and rolled back, so in-process callers (tests,
        bench) see their own environment afterwards."""
        from tpu_syncbn.audit import __main__ as cli

        monkeypatch.setenv("XLA_FLAGS", "--caller_flag")
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        cli._force_env()
        assert cli._DEVCOUNT_FLAG in os.environ["XLA_FLAGS"]
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        cli._restore_env()
        assert os.environ["XLA_FLAGS"] == "--caller_flag"
        assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
        assert cli._FORCED_ENV == {}

    def test_env_restore_keeps_a_callers_later_change(self, monkeypatch):
        from tpu_syncbn.audit import __main__ as cli

        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        monkeypatch.setenv(
            "XLA_FLAGS", cli._DEVCOUNT_FLAG
        )  # already forced: left alone
        cli._force_env()
        os.environ["JAX_PLATFORMS"] = "caller-took-over"
        cli._restore_env()
        # our value was replaced by the caller: restoration backs off
        assert os.environ["JAX_PLATFORMS"] == "caller-took-over"
        assert cli._FORCED_ENV == {}

    def test_lint_only_main_runs_in_process_without_env_leak(
        self, capsys
    ):
        from tpu_syncbn.audit import __main__ as cli

        before = (os.environ.get("XLA_FLAGS"),
                  os.environ.get("JAX_PLATFORMS"))
        rc = cli.main(["--no-contracts", "--json"])
        assert rc == 0
        json.loads(capsys.readouterr().out)  # valid report
        assert (os.environ.get("XLA_FLAGS"),
                os.environ.get("JAX_PLATFORMS")) == before
