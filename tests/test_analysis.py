"""repro.analysis — the §15 static-analysis gate's own tests.

Three layers:

  * fabricated-jaxpr unit tests: one positive and one negative program
    per determinism rule, traced with ``jax.make_jaxpr`` so the rules
    are exercised against REAL jaxprs, not mocks;
  * seeded mutations (acceptance criteria): a fold-like function with a
    second scatter must trip the single-scatter invariant, and an
    oversized fabricated block plan must trip ``vmem-overflow``;
  * the real tree: the full gate over the repo must be clean, the fold
    artifacts must carry exactly one scatter per state leaf on the
    single-host AND (on mesh CI legs) the shard_mapped path, and the
    solve_attach footprint must match hand-computed bytes at both
    ladder extremes.

Mesh-matrix legs (2 and 8 forced devices) run the sharded audit in
process; the tier-1 leg covers it via a forced-device subprocess child
(the test_plane.py idiom).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import determinism, imports, kernels, lint, visitor
from repro.analysis.visitor import Finding

NDEV = jax.device_count()


def _audit(fn, *args, contract=None, name="t"):
    return determinism.audit_jaxpr(jax.make_jaxpr(fn)(*args), name,
                                   contract or determinism.Contract())


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------- determinism: rules ------


class TestDeterminismRules:
    def test_float_scatter_add_flagged(self):
        def f(x, idx):
            return jnp.zeros((8,), jnp.float32).at[idx].add(x)
        fs = _audit(f, jnp.ones((4,), jnp.float32),
                    jnp.zeros((4,), jnp.int32))
        assert "float-scatter-add" in _rules(fs)

    def test_int_scatter_add_clean(self):
        def f(x, idx):
            return jnp.zeros((8,), jnp.int32).at[idx].add(x)
        fs = _audit(f, jnp.ones((4,), jnp.int32),
                    jnp.zeros((4,), jnp.int32))
        assert "float-scatter-add" not in _rules(fs)

    def test_iota_indexed_scatter_add_clean(self):
        # statically-unique indices: a pure iota never collides
        def f(x):
            idx = jax.lax.iota(jnp.int32, 4)
            return jnp.zeros((8,), jnp.float32).at[idx].add(x)
        assert _audit(f, jnp.ones((4,), jnp.float32)) == []

    def test_overwrite_scatter_clean(self):
        def f(x, idx):
            return jnp.zeros((8,), jnp.float32).at[idx].set(x)
        fs = _audit(f, jnp.ones((4,), jnp.float32),
                    jnp.zeros((4,), jnp.int32))
        assert "float-scatter-add" not in _rules(fs)

    def test_implicit_rng_flagged(self):
        def f(x):
            return x + jax.lax.rng_uniform(0.0, 1.0, (4,))
        assert "implicit-rng" in _rules(_audit(f, jnp.ones((4,))))

    def test_unthreaded_key_flagged(self):
        # PRNGKey(0) inside the trace: the seed reaches no invar
        def f(x):
            return x + jax.random.uniform(jax.random.PRNGKey(0), (4,))
        assert "rng-unthreaded-key" in _rules(_audit(f, jnp.ones((4,))))

    def test_threaded_key_clean(self):
        def f(key, x):
            return x + jax.random.uniform(key, (4,))
        fs = _audit(f, jax.random.PRNGKey(0), jnp.ones((4,)))
        assert "rng-unthreaded-key" not in _rules(fs)
        assert "implicit-rng" not in _rules(fs)

    @pytest.mark.skipif(NDEV < 2, reason="needs >1 device")
    def test_float_psum_flagged_and_allowlisted(self):
        from repro.utils.compat import make_mesh, shard_map
        mesh = make_mesh((NDEV,), ("data",))
        fn = shard_map(
            lambda x: jax.lax.psum(x, "data"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec("data"),
            out_specs=jax.sharding.PartitionSpec())
        x = jnp.ones((NDEV, 4), jnp.float32)
        fs = _audit(fn, x)
        assert "unordered-collective" in _rules(fs)
        assert "contract-collective" in _rules(fs)
        # Allowlisting clears the contract rule and demotes the
        # FP-order finding to suppressed (visible, non-gating).
        ok = _audit(fn, x, contract=determinism.Contract(
            allow_collectives=frozenset({"psum"})))
        assert "contract-collective" not in _rules(ok)
        assert all(f.suppressed for f in ok
                   if f.rule == "unordered-collective")


# --------------------------------- determinism: fold invariant -------


def _fold_like(extra_scatter):
    """A miniature fold: FILL_OR_DROP overwrite scatters into 2 state
    leaves, indexed by the same data-derived slot vector."""
    def f(centers, mass, slots, new_c, new_m):
        centers = centers.at[slots].set(new_c, mode="drop")
        mass = mass.at[slots].set(new_m, mode="drop")
        if extra_scatter:
            mass = mass.at[slots].set(new_m * 2.0, mode="drop")
        return centers, mass
    return f


def _fold_args():
    return (jnp.zeros((8, 4), jnp.float32), jnp.zeros((8,), jnp.float32),
            jnp.zeros((3,), jnp.int32), jnp.ones((3, 4), jnp.float32),
            jnp.ones((3,), jnp.float32))


class TestFoldInvariant:
    def test_conforming_fold_clean(self):
        fs = _audit(_fold_like(False), *_fold_args(),
                    contract=determinism.Contract(fold_leaves=2))
        assert fs == []

    def test_seeded_second_scatter_caught(self):
        # acceptance criterion: a mutated fold with one extra scatter
        # must violate the structural count
        fs = _audit(_fold_like(True), *_fold_args(),
                    contract=determinism.Contract(fold_leaves=2))
        assert "fold-single-scatter" in _rules(fs)

    def test_accumulating_fold_caught(self):
        def f(mass, slots, w):
            return mass.at[slots].add(w, mode="drop")
        fs = _audit(f, jnp.zeros((8,), jnp.float32),
                    jnp.zeros((3,), jnp.int32), jnp.ones((3,)),
                    contract=determinism.Contract(fold_leaves=1))
        assert "fold-single-scatter" in _rules(fs)


# ----------------------------------------- determinism: real tree ----


class TestRealArtifacts:
    def test_gate_clean_on_tree(self):
        findings, audited, skipped = determinism.audit_all()
        assert [f for f in findings if not f.suppressed] == [], findings
        assert {"serve_step", "fold", "finalize",
                "split_retire"} <= set(audited)
        if NDEV > 1:
            assert "fold_sharded" in audited
        else:
            assert "fold_sharded" in skipped

    def test_fold_is_exactly_one_scatter_per_leaf(self):
        """The invariant stated structurally: the single-host fold
        jaxpr carries exactly len(ServerState) overwrite scatters and
        zero accumulating ones."""
        arts = {a.name: a
                for a in determinism.trace_artifacts(include_sharded=False)[0]}
        leaves = determinism.n_fold_leaves()
        names = [s.eqn.primitive.name
                 for s in visitor.iter_eqns(arts["fold"].closed_jaxpr)]
        assert names.count("scatter") == leaves
        assert not any(n in determinism.ACCUM_SCATTERS for n in names)

    @pytest.mark.skipif(NDEV < 2, reason="mesh CI legs (2 and 8 devices)")
    def test_sharded_fold_single_scatter_and_allgather_only(self):
        """Mesh-matrix acceptance: the shard_mapped fold is all_gather
        + the same per-leaf overwrite scatters — audited at whatever
        device count the CI leg forces (2 and 8)."""
        arts = {a.name: a
                for a in determinism.trace_artifacts(include_sharded=True)[0]}
        art = arts["fold_sharded"]
        assert determinism.audit_jaxpr(
            art.closed_jaxpr, art.name, art.contract) == []
        names = [s.eqn.primitive.name
                 for s in visitor.iter_eqns(art.closed_jaxpr)]
        assert names.count("scatter") == determinism.n_fold_leaves()
        assert not any(n in determinism.ACCUM_SCATTERS for n in names)

    def test_aggregate_sharded_no_scatter_add(self):
        """Regression for the fixed real finding: the one-shot sharded
        aggregation (M0 seeding) no longer accumulates via scatter."""
        pytest.importorskip("repro.core.distributed")
        from repro.core import server
        def agg(pts, mask):
            return server.aggregate(pts, mask, k=4)
        jaxpr = jax.make_jaxpr(agg)(
            jnp.zeros((2, 3, 5), jnp.float32), jnp.ones((2, 3), bool))
        names = [s.eqn.primitive.name for s in visitor.iter_eqns(jaxpr)]
        assert "scatter-add" not in names


# ------------------------------------------------- kernels pass ------


class TestKernelChecker:
    def test_ladder_clean(self):
        findings, n_plans = kernels.audit_all()
        assert findings == []
        assert n_plans >= 20

    def test_solve_attach_footprint_ladder_extremes(self):
        """Hand-computed VMEM bytes at both ends of the rung ladder
        (B=8 grid row; padded shapes; x2 streaming double-buffer,
        tau resident x1)."""
        from repro.kernels import solve_attach
        for n, d, kp, k in ((64, 64, 4, 16), (1024, 512, 8, 128)):
            plan = solve_attach.block_plan(8, n, d, kp, k, dtype="f32")
            npad = ((n + 7) // 8) * 8
            dpad = ((d + 127) // 128) * 128
            kppad = ((kp + 127) // 128) * 128
            kpad = ((k + 127) // 128) * 128
            expect = (
                2 * (npad * dpad            # x block
                     + kppad * dpad         # theta0
                     + kppad + npad         # center_mask + point_mask
                     + npad + npad          # labels + min_dists
                     + kppad * dpad + kppad)  # centers + center_labels
                * 4
                + kpad * dpad * 4)          # tau: resident, single
            assert kernels.footprint_bytes(plan) == expect, (n, d)

    def test_seeded_oversized_plan_caught(self):
        # acceptance criterion: a fabricated plan past the budget
        plan = {"kernel": "fab", "grid": (1,), "storage": "f32",
                "accum": "f32",
                "blocks": [{"name": "x", "shape": (4096, 1024),
                            "dtype": "f32", "kind": "in",
                            "array_shape": (4096, 1024)}]}
        hw = {"vmem_bytes": 16 * 2 ** 20}
        assert _rules(kernels.check_plan(plan, hw)) == ["vmem-overflow"]

    def test_lane_and_sublane_lint(self):
        hw = {"vmem_bytes": 1 << 40}
        bad = {"kernel": "fab", "grid": (2, 2), "storage": "f32",
               "accum": "f32",
               "blocks": [{"name": "x", "shape": (4, 100), "dtype": "f32",
                           "kind": "in", "array_shape": (64, 1000)}]}
        assert _rules(kernels.check_plan(bad, hw)) == [
            "lane-misaligned", "sublane-misaligned"]
        # unpartitioned dims only pad — no findings
        ok = dict(bad, blocks=[dict(bad["blocks"][0],
                                    array_shape=(4, 100))])
        assert kernels.check_plan(ok, hw) == []
        # a one-row window over a (rows, d) array is refused like any
        # partial sublane tile; the (rows, 1, d) gather layout is clean
        granule = dict(bad, blocks=[{"name": "x", "shape": (1, 128),
                                     "dtype": "f32", "kind": "in",
                                     "array_shape": (64, 128)}])
        assert _rules(kernels.check_plan(granule, hw)) == [
            "sublane-misaligned"]
        gather = dict(bad, blocks=[{"name": "x", "shape": (1, 1, 128),
                                    "dtype": "f32", "kind": "in",
                                    "array_shape": (64, 1, 128)}])
        assert kernels.check_plan(gather, hw) == []

    @pytest.mark.parametrize("block,array,rules", [
        ((128,), (4096,), ["short-1d-block"]),   # shorter than T(1024)
        ((256,), (4096,), ["short-1d-block"]),
        ((2048,), (4096,), []),                  # whole layout tiles
        ((4096,), (4096,), []),                  # unpartitioned
        ((100,), (100,), []),
    ])
    def test_short_1d_block_lint(self, block, array, rules):
        """1-D blocks that cut an array below XLA's 1-D layout tile are
        what the compiler refused in pdist_argmin / kmeans_update."""
        hw = {"vmem_bytes": 1 << 40}
        plan = {"kernel": "fab", "grid": (2,), "storage": "f32",
                "accum": "f32",
                "blocks": [{"name": "idx", "shape": block, "dtype": "i32",
                            "kind": "out", "array_shape": array}]}
        assert _rules(kernels.check_plan(plan, hw)) == rules

    def test_bf16_accum_rule(self):
        hw = {"vmem_bytes": 1 << 40}
        plan = {"kernel": "fab", "grid": (1,), "storage": "bf16",
                "accum": "bf16", "blocks": []}
        assert _rules(kernels.check_plan(plan, hw)) == ["bf16-accum"]
        plan["accum"] = "f32"
        assert kernels.check_plan(plan, hw) == []


# ---------------------------------------------------- lint pass ------


class TestLint:
    def test_tracer_branch_pos_neg(self):
        pos = ("import jax.numpy as jnp\n"
               "def f(x):\n"
               "    y = jnp.sum(x)\n"
               "    if y > 0:\n"
               "        return 1\n")
        assert _rules(lint.scan_source(pos, "t.py")) == ["tracer-branch"]
        neg = ("import jax.numpy as jnp\n"
               "def f(x, flag):\n"
               "    y = jnp.sum(x)\n"
               "    if x is not None and flag:\n"
               "        return int(x.shape[0])\n")
        assert lint.scan_source(neg, "t.py") == []

    def test_tracer_coercion_and_materializer(self):
        pos = ("import jax.numpy as jnp\n"
               "def f(x):\n"
               "    return float(jnp.mean(x))\n")
        assert _rules(lint.scan_source(pos, "t.py")) == ["tracer-coercion"]
        neg = ("import numpy as np\nimport jax.numpy as jnp\n"
               "def f(x):\n"
               "    return float(np.asarray(jnp.mean(x)))\n")
        assert lint.scan_source(neg, "t.py") == []

    def test_suppression_comment(self):
        src = ("import jax.numpy as jnp\n"
               "def f(x):\n"
               "    y = jnp.sum(x)\n"
               "    if y > 0:  # repro: allow(tracer-branch)\n"
               "        return 1\n")
        (f,) = lint.scan_source(src, "t.py")
        assert f.suppressed
        # a different rule name does NOT suppress
        src2 = src.replace("allow(tracer-branch)", "allow(tracer-coercion)")
        (f2,) = lint.scan_source(src2, "t.py")
        assert not f2.suppressed

    def test_static_unhashable(self):
        src = ("import jax\n"
               "@jax.jit(static_argnames=('opts',))\n"
               "def f(x, opts=[1, 2]):\n"
               "    return x\n")
        assert _rules(lint.scan_source(src, "t.py")) == ["static-unhashable"]

    def test_checkpoint_bypass(self):
        src = ("import numpy as np\n"
               "def f(x):\n"
               "    np.savez('out.npz', x=x)\n")
        assert _rules(lint.scan_source(src, "t.py")) == ["checkpoint-bypass"]
        assert lint.scan_source(src, "repro/checkpoint/store.py") == []

    def test_tree_clean(self):
        findings, n = lint.audit_all()
        assert n > 50
        assert [f for f in findings if not f.suppressed] == []


# ------------------------------------------------- imports pass ------


class TestImports:
    def test_report_shape(self):
        rep = imports.report()
        assert rep["modules"] > 50
        # the live serve scaffold stays reachable...
        assert "repro.models.model" in rep["reachable"]
        # ...and every unreachable candidate is zoo-only, never core
        assert all(m.startswith(("repro.models.", "repro.configs."))
                   for m in rep["unreachable"])
        assert imports.render(rep)


# ------------------------------------------------------- the CLI -----


def _run_cli(*argv, env_extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-m", "repro.analysis", *argv],
                          env=env, capture_output=True, text=True,
                          timeout=900)


@pytest.mark.slow
def test_cli_unknown_pass_exits_2():
    out = _run_cli("--only", "nosuchpass")
    assert out.returncode == 2
    assert "valid passes:" in out.stderr
    assert "determinism" in out.stderr


@pytest.mark.slow
def test_cli_json_gate_clean(tmp_path):
    """The CI invocation: --all --json must exit 0 on this tree with a
    parseable report, including sharded artifacts when forced devices
    are available (the tier-1 leg's mesh coverage)."""
    out = _run_cli("--all", "--json", env_extra={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert out.returncode == 0, out.stderr[-4000:]
    payload = json.loads(out.stdout)
    assert payload["ok"] is True
    passes = payload["passes"]
    assert passes["determinism"]["gated"] is True
    assert "fold_sharded" in passes["determinism"]["audited"]
    assert passes["imports"]["gated"] is False
    assert passes["kernels"]["plans"] >= 20


def test_finding_serialization():
    f = Finding("lint", "tracer-branch", "x.py:3", "msg", suppressed=True)
    d = f.to_dict()
    assert d == {"pass": "lint", "rule": "tracer-branch", "where": "x.py:3",
                 "message": "msg", "suppressed": True}
    assert "tracer-branch" in str(f) and "(suppressed)" in str(f)
