"""make_lgc_train_step equivalence ladder (the 100M-stack engine rung).

Same discipline as tests/test_tasks.py, applied to the shard_map step the
qwen2_100m task drives: the sparse and bucket uplinks must reproduce the
dense server sum, at every mesh size the process can build ({1, 8} when
the test-sharded lane forces 8 host devices), under a static and a
gilbert_flaky multi-channel scenario.

At SATURATING sparsity -- cumulative channel budgets clamped to the leaf
size, i.e. every coordinate is transmitted -- dense_masked, sparse_gather,
bucket_sparse and the FedAvg baseline are the same algorithm, so their
trajectories must agree BIT-FOR-BIT on a 1-device mesh (no histogram-tie
or top_k-order escape hatches) and to reduction-order rounding on larger
meshes (the dense server sum is an XLA all-reduce; the sparse paths
accumulate gathered shards sequentially -- same addends, different order).  Non-saturating selection is pinned at the leaf level
with a distinct-bin magnitude construction where histogram selection is
provably exact.

Also here: the k-budget cumulative clamp (_leaf_ks) that used to let a
64-element bias at sparsity (0.01, 0.02, 0.02) request 3 coordinates, the
Pallas-vs-oracle backend parity, the delivery-mask freeze (nothing
delivered => params bit-frozen, error memory grows), and the per-device
stacked EF rows.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.kernels import ref as kref
from repro.launch import compat
from repro.launch.mesh import fl_axis_name, make_host_mesh
from repro.launch.steps import (_compress_leaf_bucket, _compress_leaf_dense,
                                _compress_leaf_sparse, _leaf_ks,
                                _row_thresholds,
                                lgc_wire_bytes_per_round, LGCStepConfig)
from repro.models.lgc_transformer import make_qwen2_100m_task
from repro.models.paper_models import ENGINE_TASKS, TASKS, make_task

N_DEV = len(jax.devices())
MESHES = sorted({1, N_DEV})
SATURATING = (1.0, 0.5, 0.5)     # cum clamp => every coordinate transmitted

TINY = dataclasses.replace(
    get_smoke_config("qwen2-100m"), name="qwen2-tiny", n_layers=1,
    d_model=32, n_heads=2, n_kv_heads=1, d_ff=64, vocab_size=64,
    attn_q_chunk=16, loss_chunk=16)

_RUNS: dict = {}


def _traj(mesh_n: int, aggregate: str, scenario=None,
          sparsity=SATURATING, backend="exact", rounds=4, **kw):
    """Cached (losses, final params, final ef) for one configuration."""
    key = (mesh_n, aggregate, scenario, sparsity, backend, rounds,
           tuple(sorted(kw.items())))
    if key not in _RUNS:
        t = make_qwen2_100m_task(m_devices=mesh_n, arch=TINY,
                                 aggregate=aggregate, sparsity=sparsity,
                                 scenario=scenario, local_steps=2, seq=16,
                                 backend=backend, **kw)
        out = t.run(rounds)
        _RUNS[key] = (out["losses"], jax.device_get(t._built["params"]),
                      jax.device_get(t._built["ef"]))
    return _RUNS[key]


def _assert_tree_bits_equal(a, b, msg=""):
    for (pa, la), (pb, lb) in zip(jax.tree_util.tree_leaves_with_path(a),
                                  jax.tree_util.tree_leaves_with_path(b)):
        xa, xb = np.asarray(la), np.asarray(lb)
        if xa.dtype == jnp.bfloat16:
            xa, xb = xa.view(np.uint16), xb.view(np.uint16)
        np.testing.assert_array_equal(xa, xb, err_msg=f"{msg}{pa}")


def _assert_tree_matches(a, b, mesh_n, msg=""):
    """Bitwise on a 1-device mesh.  On mesh > 1 the dense server sum is an
    XLA all-reduce while the sparse/bucket paths accumulate gathered shards
    sequentially -- same multiset of addends, different order -- so agreement
    is to reduction-order rounding (~1 ulp of the bf16 params)."""
    if mesh_n == 1:
        _assert_tree_bits_equal(a, b, msg)
        return
    for (pa, la), (pb, lb) in zip(jax.tree_util.tree_leaves_with_path(a),
                                  jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_allclose(
            np.asarray(la, np.float32), np.asarray(lb, np.float32),
            atol=1e-3, rtol=1e-2, err_msg=f"{msg}{pa}")


def _assert_losses_match(l1, l2, mesh_n):
    if mesh_n == 1:
        assert l1 == l2
    else:
        np.testing.assert_allclose(l1, l2, atol=1e-4)


class TestSmallLeafBudgets:
    """The satellite bugfix: per-channel ks are cumulatively clamped."""

    def test_64_element_bias_keeps_channels_disjoint(self):
        # naive max(1, int(64*f)) would be [1, 1, 1] too -- but ONLY because
        # of the clamp discipline does the invariant below hold for it
        assert _leaf_ks(64, (0.01, 0.02, 0.02)) == [1, 1, 1]

    def test_two_element_leaf_overflow_channels_go_empty(self):
        # naive floors request 3 coords from a 2-element leaf
        assert _leaf_ks(2, (0.9, 0.9, 0.9)) == [1, 1, 0]

    def test_saturating_first_channel_takes_all(self):
        assert _leaf_ks(10, SATURATING) == [10, 0, 0]

    def test_cumulative_budget_never_exceeds_leaf(self):
        rng = np.random.default_rng(0)
        for _ in range(200):
            size = int(rng.integers(1, 500))
            c = int(rng.integers(1, 5))
            fr = tuple(float(f) for f in rng.uniform(0, 1.2, c))
            ks = _leaf_ks(size, fr)
            assert sum(ks) <= size
            assert all(k >= 0 for k in ks)
            assert ks[0] >= 1                      # at least one coordinate

    def test_wire_accounting_uses_clamped_budgets(self):
        params = {"w": jnp.zeros(64), "b": jnp.zeros(2)}
        cfg = LGCStepConfig(sparsity=(0.01, 0.02, 0.02))
        wire = lgc_wire_bytes_per_round(params, cfg)
        # 64-leaf: [1,1,1]; 2-leaf: [1,1,0]  => 5 coords * (4+4) bytes
        assert wire["sparse_gather"] == wire["bucket_sparse"] == 5 * 8
        assert wire["none"] == 66 * 4
        assert wire["dense_masked"] == 66 * 4      # f32 psum default


class TestUplinkEquivalence:
    """sparse/bucket uplinks == dense server sum, mesh {1, N_DEV}."""

    @pytest.mark.parametrize("mesh_n", MESHES)
    @pytest.mark.parametrize("aggregate", ["sparse_gather", "bucket_sparse",
                                           "none"])
    def test_static_saturating_matches_dense_bitwise(self, mesh_n, aggregate):
        """Everything transmitted => all four aggregates are the same
        algorithm; trajectories must agree to the last bit on a 1-device
        mesh (reduction-order rounding on larger ones)."""
        ref_l, ref_p, _ = _traj(mesh_n, "dense_masked")
        l, p, _ = _traj(mesh_n, aggregate)
        _assert_losses_match(l, ref_l, mesh_n)
        _assert_tree_matches(p, ref_p, mesh_n, f"{aggregate}@{mesh_n}: ")

    @pytest.mark.parametrize("mesh_n", MESHES)
    @pytest.mark.parametrize("aggregate", ["sparse_gather", "bucket_sparse"])
    def test_flaky_channel_masks_match_dense_bitwise(self, mesh_n, aggregate):
        """gilbert_flaky delivery masks thread identically through all
        compressed uplinks: undelivered mass stays in EF on every path."""
        ref = _traj(mesh_n, "dense_masked", scenario="gilbert_flaky",
                    sparsity=(1.0,))
        got = _traj(mesh_n, aggregate, scenario="gilbert_flaky",
                    sparsity=(1.0,))
        _assert_losses_match(got[0], ref[0], mesh_n)
        _assert_tree_matches(got[1], ref[1], mesh_n, f"{aggregate}@{mesh_n}: ")
        _assert_tree_matches(got[2], ref[2], mesh_n,
                             f"ef {aggregate}@{mesh_n}: ")

    @pytest.mark.parametrize("mesh_n", MESHES)
    def test_learns_with_real_compression(self, mesh_n):
        """Non-saturating sparse_gather at tiny scale still learns (mean of
        first 3 vs last 3 rounds -- single-round noise is real here)."""
        l, _, _ = _traj(mesh_n, "sparse_gather", sparsity=(0.05, 0.1, 0.1),
                        rounds=20, local_lr=5e-3)
        assert np.isfinite(l).all()
        assert np.mean(l[-3:]) < np.mean(l[:3])


def _run_leaf(fn, e, d, sparsity, recv, **kw):
    """Run one leaf compressor inside a 1-device shard_map (the sparse and
    bucket paths issue all_gathers, so they need a mapped axis)."""
    mesh = make_host_mesh(1)
    fl_ax = fl_axis_name(mesh)
    f = compat.shard_map(
        lambda e_, d_, r_: fn(e_, d_, sparsity, r_, fl_ax, 1, **kw),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
        axis_names={fl_ax})
    # partial-auto shard_map only lowers under jit on the pinned jax
    return jax.jit(f)(e, d, recv)


class TestLeafLevelSelection:
    """Non-saturating selection, pinned where it is provably exact: 64
    linear-spaced magnitudes occupy 64 distinct histogram bins, so the
    256-bin threshold rule selects EXACTLY the top cum-k ranks."""

    COLS = 64
    SP = (0.1, 0.2)          # ks = [6, 12] -> ranks 0-5 / 6-17

    def _u(self):
        # half-integer magnitudes: each lands strictly INSIDE its own
        # 256-bin histogram bucket, so no value ever sits on a threshold
        # edge (selection is strict >) and every rank cut is exact
        rng = np.random.default_rng(7)
        mag = np.arange(self.COLS, dtype=np.float32) + 1.5
        sign = np.where(rng.integers(0, 2, self.COLS), 1.0, -1.0)
        return jnp.asarray(rng.permutation(mag) * sign)

    def test_sparse_equals_dense_oracle(self):
        u = self._u()
        e, d = jnp.zeros_like(u), u
        recv = jnp.ones(2, jnp.int32)
        g_d, e_d = _compress_leaf_dense(e, d, self.SP, recv)
        g_s, e_s = _run_leaf(_compress_leaf_sparse, e, d, self.SP, recv)
        np.testing.assert_array_equal(np.asarray(g_d), np.asarray(g_s))
        np.testing.assert_array_equal(np.asarray(e_d), np.asarray(e_s))
        # and the selection is the exact top-18 by |u|
        assert int((g_d != 0).sum()) == 18
        kept = np.abs(np.asarray(u))[np.asarray(g_d) != 0]
        assert kept.min() == self.COLS - 18 + 1.5

    def test_masked_channel_stays_in_error_memory(self):
        """recv = (1, 0): channel 1's 12 coordinates are selected but not
        delivered -- g carries only channel 0, EF keeps the rest."""
        u = self._u()
        e, d = jnp.zeros_like(u), u
        recv = jnp.asarray([1, 0], jnp.int32)
        g_d, e_d = _compress_leaf_dense(e, d, self.SP, recv)
        g_s, e_s = _run_leaf(_compress_leaf_sparse, e, d, self.SP, recv)
        np.testing.assert_array_equal(np.asarray(g_d), np.asarray(g_s))
        np.testing.assert_array_equal(np.asarray(e_d), np.asarray(e_s))
        assert int((g_d != 0).sum()) == 6          # channel 0 only
        kept = np.abs(np.asarray(u))[np.asarray(g_d) != 0]
        assert kept.min() == self.COLS - 6 + 1.5

    @pytest.mark.parametrize("recv", [(1, 1), (1, 0), (0, 1), (0, 0)])
    def test_ef_conservation_all_paths(self, recv):
        """u = g_own + e_new exactly, on every path and every mask: mass is
        either on the wire or in the error memory, never dropped or doubled
        (the bucket path's seed version leaked the untransmitted tail)."""
        u = self._u()
        e = jnp.asarray(np.random.default_rng(3).normal(size=self.COLS)
                        .astype(np.float32))
        d = u
        r = jnp.asarray(recv, jnp.int32)
        tot = np.asarray(e + d)
        for name, (g, e_new) in {
            "dense": _compress_leaf_dense(e, d, self.SP, r),
            "sparse": _run_leaf(_compress_leaf_sparse, e, d, self.SP, r),
            "bucket": _run_leaf(_compress_leaf_bucket, e, d, self.SP, r),
        }.items():
            # n_fl=1: g_mean == g_own, so the identity is directly checkable
            np.testing.assert_allclose(np.asarray(g) + np.asarray(e_new),
                                       tot, atol=1e-6, err_msg=name)


def _row_leaf(case: str) -> tuple[np.ndarray, tuple]:
    """(rows, cols) f32 leaf and sparsity ladder of one threshold case."""
    rng = np.random.default_rng(sum(map(ord, case)))
    ladder = (0.01, 0.02, 0.02)
    if case == "one_row":
        u = rng.normal(size=(1, 3000))
    elif case == "many_rows":
        u = rng.normal(size=(37, 1000)) * rng.uniform(0.1, 10, (37, 1))
    elif case == "narrow":                 # under 256, not a multiple of 128
        u, ladder = rng.normal(size=(5, 200)), (0.05, 0.1, 0.1)
    elif case == "zero_row":               # maxabs 0: every threshold 0
        u = rng.normal(size=(6, 700))
        u[2] = 0.0
    elif case == "bin_edges":              # magnitudes exactly on bin edges
        mx = rng.uniform(0.5, 4.0, (8, 1))
        u = (rng.integers(0, 257, (8, 640)) * (mx / kref.N_BINS)
             * rng.choice([-1.0, 1.0], (8, 640)))
        ladder = (0.1, 0.2, 0.2)
    elif case == "half_zero":
        u = rng.normal(size=(9, 512))
        u[:, rng.permutation(512)[:256]] = 0.0
        ladder = (0.2, 0.3, 0.3)           # budgets reach into the zeros
    elif case == "clamped":                # cumulative budget clamped to cols
        u, ladder = rng.normal(size=(4, 10)), SATURATING
    elif case == "wide_range":             # magnitudes 1e-6 .. 1e2
        u = (10.0 ** rng.uniform(-6, 2, (12, 2048))
             * rng.choice([-1.0, 1.0], (12, 2048)))
    return u.astype(np.float32), ladder


class TestRowThresholds:
    """The sparse path's per-row threshold bisection (``_row_thresholds``)
    against the histogram oracle it replaces, bit for bit."""

    @pytest.mark.parametrize("case", ["one_row", "many_rows", "narrow",
                                      "zero_row", "bin_edges", "half_zero",
                                      "clamped", "wide_range"])
    def test_bisection_matches_histogram_bitwise(self, case):
        u, ladder = _row_leaf(case)
        cum = jnp.asarray(np.cumsum(_leaf_ks(u.shape[1], ladder)), jnp.int32)
        if case == "clamped":
            assert int(cum[-1]) == u.shape[1]

        @jax.jit
        def oracle(u):
            mx = jax.vmap(kref.hist_maxabs)(u)
            counts = jax.vmap(kref.hist_counts)(u, mx)
            return jax.vmap(lambda c, m: kref.hist_thresholds(c, m, cum)
                            )(counts, mx)

        @jax.jit
        def search(u):
            return _row_thresholds(u, jax.vmap(kref.hist_maxabs)(u), cum)

        want = np.asarray(oracle(jnp.asarray(u)))
        got = np.asarray(search(jnp.asarray(u)))
        assert got.shape == want.shape == (u.shape[0], cum.shape[0])
        np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
        if case == "zero_row":
            assert not want[2].any()

    def test_sparse_leaf_has_no_integer_scatter(self):
        """The vmapped histogram was a scatter-add into int32 counts (on a
        TPU: a sort of every bin index plus a scatter).  Only the f32
        scatters of g_own and g_sum may remain."""
        rows, cols, ladder = 64, 1024, (0.01, 0.02, 0.02)
        mesh = make_host_mesh(1)
        fl_ax = fl_axis_name(mesh)
        f = compat.shard_map(
            lambda e_, d_, r_: _compress_leaf_sparse(
                e_, d_, ladder, r_, fl_ax, 1, spec=P("model", None)),
            mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
            axis_names={fl_ax})
        leaf = jax.ShapeDtypeStruct((rows, cols), jnp.float32)
        jaxpr = jax.make_jaxpr(f)(leaf, leaf,
                                  jax.ShapeDtypeStruct((3,), jnp.int32))

        def scatters(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name.startswith("scatter"):
                    yield eqn.primitive.name, eqn.invars[0].aval.dtype
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from scatters(sub)

        found = list(scatters(jaxpr.jaxpr))
        assert found, "g_own/g_sum scatters should be in the jaxpr"
        assert all(dt == jnp.float32 for _, dt in found), found
        assert {name for name, _ in found} == {"scatter-add"}


class TestDeliveryMaskFreeze:
    def test_nothing_delivered_freezes_params_and_grows_ef(self):
        """received == 0 for every device and channel: the server sum is
        empty, params must not move by a single bit, and the residual mass
        keeps accumulating."""
        t = make_qwen2_100m_task(m_devices=1, arch=TINY, local_steps=2,
                                 seq=16, sparsity=(0.05, 0.1, 0.1))
        b = t.build()
        params, ef, step, pipe = b["params"], b["ef"], b["step"], b["pipe"]
        p0 = jax.device_get(params)                # donate-safe snapshot
        zeros = jnp.zeros((1, t.step_cfg.n_channels), jnp.int32)
        masses = []
        for _ in range(3):
            x, y = pipe.next_batch()
            params, ef, _ = step(params, ef, {"tokens": jnp.asarray(x),
                                              "labels": jnp.asarray(y)},
                                 zeros)
            masses.append(sum(float(jnp.sum(jnp.abs(e)))
                              for e in jax.tree_util.tree_leaves(ef)))
        _assert_tree_bits_equal(jax.device_get(params), p0)
        assert masses[0] > 0 and masses[2] > masses[1] > masses[0]


class TestPallasBackend:
    def test_pallas_backend_bitwise_matches_oracle(self):
        """backend="pallas" with the routing floor lowered to 1 sends every
        dense-path leaf through kernels.lgc_compress_hist; the trajectory
        must be bit-identical to the exact kref oracle."""
        ref = _traj(1, "dense_masked", sparsity=(0.05, 0.1, 0.1), rounds=3)
        got = _traj(1, "dense_masked", sparsity=(0.05, 0.1, 0.1), rounds=3,
                    backend="pallas", pallas_min_elems=1)
        assert got[0] == ref[0]
        _assert_tree_bits_equal(got[1], ref[1], "pallas params: ")
        _assert_tree_bits_equal(got[2], ref[2], "pallas ef: ")

    def test_routing_floor_keeps_small_leaves_on_oracle(self):
        """Default PALLAS_MIN_ELEMS is far above the tiny arch's leaves, so
        backend="pallas" at the default floor is the oracle path -- still
        bit-identical (the routing threshold itself changes nothing)."""
        ref = _traj(1, "dense_masked", sparsity=(0.05, 0.1, 0.1), rounds=3)
        got = _traj(1, "dense_masked", sparsity=(0.05, 0.1, 0.1), rounds=3,
                    backend="pallas")
        assert got[0] == ref[0]
        _assert_tree_bits_equal(got[1], ref[1])


class TestStackedErrorFeedback:
    def test_ef_leaves_are_stacked_per_device(self):
        _, _, ef = _traj(MESHES[-1], "sparse_gather",
                         sparsity=(0.05, 0.1, 0.1), rounds=4)
        for leaf in jax.tree_util.tree_leaves(ef):
            assert leaf.shape[0] == MESHES[-1]

    @pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device mesh")
    def test_ef_rows_differ_across_devices(self):
        """Each FL device owns its own residual row.  The seed code's
        replicated P() spec collapsed device_get to shard 0's row -- with
        per-device data the rows MUST differ."""
        _, _, ef = _traj(N_DEV, "sparse_gather", sparsity=(0.05, 0.1, 0.1),
                         rounds=4)
        distinct = False
        for leaf in jax.tree_util.tree_leaves(ef):
            rows = np.asarray(leaf).reshape(N_DEV, -1)
            if not np.allclose(rows, rows[0:1]):
                distinct = True
        assert distinct


class TestRegistry100m:
    def test_qwen2_100m_is_registered(self):
        assert "qwen2_100m" in TASKS
        spec = TASKS["qwen2_100m"]
        assert spec.dataset == "tokens" and not spec.is_engine_task

    def test_engine_tasks_excludes_the_token_stack(self):
        assert set(ENGINE_TASKS) == {"lr_mnist", "cnn_mnist",
                                     "rnn_shakespeare"}
        assert "qwen2_100m" not in ENGINE_TASKS

    def test_make_task_smoke_builds(self):
        t = make_task("qwen2_100m", m_devices=1, preset="smoke")
        assert t.n_devices == 1
        assert t.param_count() > 100_000

    def test_full_preset_is_a_real_100m(self):
        """The tentpole number: >= 1e8 flattened gradient elements, every
        matmul leaf above the Pallas routing floor (eval_shape only -- no
        128M-param init in the test lane)."""
        from repro.core.compressor import PALLAS_MIN_ELEMS
        t = make_task("qwen2_100m", m_devices=8)
        assert t.param_count() >= 100_000_000
        assert t.step_cfg.backend == "pallas"
        assert t.step_cfg.pallas_min_elems == PALLAS_MIN_ELEMS
        d = t.arch.d_model
        assert d * d >= PALLAS_MIN_ELEMS // 8      # attn leaves route

    def test_wire_accounting_is_published(self):
        t = make_task("qwen2_100m", m_devices=8)
        dense = t.param_count() * 4
        sparse = t.wire_bytes_per_round()
        assert 0 < sparse < dense / 10             # >10x wire reduction
