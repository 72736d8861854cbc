"""Tests for the device-parallel simulation farm (``repro.noc.farm``).

Tier (a): ``sweep(devices=n)`` shard_maps the spec grid across the
device mesh — asserted bit-identical to the vmapped single-device path,
including uneven grids that exercise the pad-and-slice masking.
Tier (b): ``simulate(..., shard=RowShard(n))`` spatially shards a
mesh's router rows with per-cycle halo exchange — asserted
flit-for-flit identical to the unsharded engine on mesh AND torus with
mixed read/write traffic.

Also covers the satellite work riding this PR: the vectorized
route-table compile path (byte-identity against a straightforward
reference expansion on 32x32 fabrics), the farm compile cache, and the
fused kernel's VMEM budget check.

Multi-device cases run in-process when the interpreter already sees
several host devices (the CI farm lane sets
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and skip on a
single-device run; one subprocess test keeps tier-1 coverage of the
halo exchange even without the lane.
"""
import numpy as np
import pytest

from conftest import given, settings, st

import jax

from repro.noc import (Mesh, NocSpec, RoutingPolicy, RowShard, Torus,
                       Workload, farm_batch, merge_spec, partition_spec,
                       sim_cache_clear, sim_cache_stats, simulate, sweep)

CLASS_FIELDS = ("done", "avg_lat", "max_lat", "beats_rx", "eff_bw",
                "w_done", "w_avg_lat", "w_max_lat", "w_beats_rx",
                "w_eff_bw")


def assert_results_equal(a, b, ctx=""):
    """Bit-exact SimResult comparison: every class stat, per-channel
    link moves + VC occupancy, and the liveness scalars."""
    assert set(a.classes) == set(b.classes), ctx
    for cname in a.classes:
        for f in CLASS_FIELDS:
            np.testing.assert_array_equal(
                getattr(a.classes[cname], f), getattr(b.classes[cname], f),
                err_msg=f"{ctx}:{cname}.{f}")
    for ch in a.channels:
        np.testing.assert_array_equal(
            a.channels[ch].link_moves, b.channels[ch].link_moves,
            err_msg=f"{ctx}:{ch}.link_moves")
        np.testing.assert_array_equal(
            a.channels[ch].vc_occupancy, b.channels[ch].vc_occupancy,
            err_msg=f"{ctx}:{ch}.vc_occupancy")
    np.testing.assert_array_equal(np.asarray(a.drained),
                                  np.asarray(b.drained), err_msg=ctx)
    np.testing.assert_array_equal(np.asarray(a.max_stall_cycles),
                                  np.asarray(b.max_stall_cycles),
                                  err_msg=ctx)


# --------------------------------------------------------------------- #
# static / dynamic partition round trip
# --------------------------------------------------------------------- #
def _spec_variants():
    rng = np.random.default_rng(7)
    out = []
    for preset in (NocSpec.narrow_wide, NocSpec.wide_only):
        for _ in range(6):
            out.append(preset(
                int(rng.integers(2, 5)), int(rng.integers(1, 5)),
                depth=int(rng.integers(1, 7)),
                burstlen=int(rng.choice([4, 16, 32])),
                service_lat=int(rng.integers(1, 20)),
                cycles=int(rng.integers(100, 500)),
                max_wide_outstanding=int(rng.integers(1, 9))))
    out.append(NocSpec.multi_stream(3, 3, n_wide=2, cycles=300))
    out.append(NocSpec.narrow_wide(4, 4, topology=Torus(4, 4),
                                   routing=RoutingPolicy.xy(2), cycles=200))
    out.append(NocSpec.narrow_wide(6, 2, topology=Mesh(6, 2, express=(2,)),
                                   cycles=200))
    return out


def test_partition_merge_round_trip_variants():
    for spec in _spec_variants():
        static, dyn = partition_spec(spec)
        assert hash(static) is not None       # the compile-cache key
        back = merge_spec(static, dyn)
        assert back == spec, spec
        # the static half is depth-normalized: any two depth variants
        # of one spec share it (that is what makes a sweep one compile)
        other = merge_spec(static, {**dyn,
                                    "depths": dyn["depths"] * 0 + 1})
        assert partition_spec(other)[0] == static


@settings(max_examples=40, deadline=None)
@given(nx=st.integers(2, 5), ny=st.integers(1, 4),
       depth=st.integers(1, 8), burstlen=st.sampled_from([4, 16, 32]),
       service_lat=st.integers(1, 24), wide=st.booleans())
def test_partition_merge_round_trip_property(nx, ny, depth, burstlen,
                                             service_lat, wide):
    preset = NocSpec.wide_only if wide else NocSpec.narrow_wide
    spec = preset(nx, ny, depth=depth, burstlen=burstlen,
                  service_lat=service_lat, cycles=200)
    static, dyn = partition_spec(spec)
    assert merge_spec(static, dyn) == spec


def test_merge_spec_rejects_bad_depths():
    static, dyn = partition_spec(NocSpec.narrow_wide(2, 2, cycles=100))
    with pytest.raises(ValueError, match="depths shape"):
        merge_spec(static, {**dyn, "depths": np.ones(17, np.int64)})


# --------------------------------------------------------------------- #
# tier (a): sharded sweep == vmapped sweep
# --------------------------------------------------------------------- #
def _sweep_points(n=6, cycles=400):
    pts = []
    for i in range(n):
        spec = NocSpec.narrow_wide(4, 4, depth=(2, 3, 4)[i % 3],
                                   cycles=cycles)
        wl = Workload.make("uniform_random",
                           rates={"narrow": 0.1, "wide": 0.5},
                           counts={"narrow": 3, "wide": 2}, seed=i)
        pts.append((spec, wl))
    return pts


def test_sweep_devices1_bit_identical():
    pts = _sweep_points()
    ref = sweep(pts)
    farm = sweep(pts, devices=1)
    assert len(ref) == len(farm) == len(pts)
    for i, (r, f) in enumerate(zip(ref, farm)):
        assert_results_equal(r, f, ctx=f"point{i}")


def test_farm_sweep_caches_per_device_count():
    pts = _sweep_points(n=4)
    sim_cache_clear()
    sweep(pts, devices=1)
    misses = sim_cache_stats()["misses"]
    assert misses == 2      # inner engine build + farm shard_map wrapper
    sweep(pts, devices=1)   # repeat sweep: pure cache hit
    assert sim_cache_stats()["misses"] == misses
    assert "farm[1]:jnp" in sim_cache_stats()["partitions"]


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (CI farm lane)")
def test_sweep_multi_device_bit_identical_with_padding():
    # 5 points on 2 devices: pads to 6, slices back — masking must be
    # invisible in every stat
    pts = _sweep_points(n=5)
    ref = sweep(pts)
    farm = sweep(pts, devices=2)
    for i, (r, f) in enumerate(zip(ref, farm)):
        assert_results_equal(r, f, ctx=f"point{i}")


def test_farm_batch_rejects_missing_devices():
    pts = _sweep_points(n=4)
    n = jax.device_count() + 1
    with pytest.raises(ValueError, match="host_platform_device_count"):
        farm_batch([s for s, _ in pts], [w for _, w in pts], devices=n)


# --------------------------------------------------------------------- #
# tier (b): row-sharded simulate == single-device simulate
# --------------------------------------------------------------------- #
def _mixed_wl(seed=3):
    return Workload.make("uniform_random",
                         rates={"narrow": 0.2, "wide": 0.7},
                         counts={"narrow": 4, "wide": 3},
                         seed=seed, write_frac=0.5)


def _mesh_spec(cycles=500):
    return NocSpec.narrow_wide(4, 4, cycles=cycles)


def _torus_spec(cycles=500):
    return NocSpec.narrow_wide(4, 4, topology=Torus(4, 4),
                               routing=RoutingPolicy.xy(2), cycles=cycles)


@pytest.mark.parametrize("mk", [_mesh_spec, _torus_spec],
                         ids=["mesh", "torus_vc"])
def test_rowshard1_flit_identical(mk):
    spec, wl = mk(), _mixed_wl()
    ref = simulate(spec, wl)
    sharded = simulate(spec, wl, shard=RowShard(1))
    assert_results_equal(ref, sharded, ctx="rowshard1")


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 devices (CI farm lane)")
@pytest.mark.parametrize("mk", [_mesh_spec, _torus_spec],
                         ids=["mesh", "torus_vc"])
def test_rowshard2_flit_identical(mk):
    spec, wl = mk(), _mixed_wl(seed=5)
    ref = simulate(spec, wl)
    sharded = simulate(spec, wl, shard=RowShard(2))
    assert_results_equal(ref, sharded, ctx="rowshard2")


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs >= 4 devices (CI farm lane)")
def test_rowshard4_flit_identical_torus():
    spec, wl = _torus_spec(), _mixed_wl(seed=11)
    ref = simulate(spec, wl)
    sharded = simulate(spec, wl, shard=RowShard(4))
    assert_results_equal(ref, sharded, ctx="rowshard4")


def test_rowshard2_flit_identical_subprocess(subproc):
    """Tier-1 coverage of the real halo exchange (2 shards, wrap and
    no-wrap) even when the main process sees one device."""
    subproc("""
        import numpy as np
        from repro.noc import (NocSpec, RoutingPolicy, RowShard, Torus,
                               Workload, simulate)
        wl = Workload.make("uniform_random",
                           rates={"narrow": 0.2, "wide": 0.7},
                           counts={"narrow": 4, "wide": 3},
                           seed=5, write_frac=0.5)
        for spec in (NocSpec.narrow_wide(4, 4, cycles=400),
                     NocSpec.narrow_wide(4, 4, topology=Torus(4, 4),
                                         routing=RoutingPolicy.xy(2),
                                         cycles=400)):
            ref = simulate(spec, wl)
            sh = simulate(spec, wl, shard=RowShard(2))
            for c in ref.classes:
                for f in ("done", "avg_lat", "max_lat", "beats_rx",
                          "w_done", "w_avg_lat", "w_beats_rx"):
                    np.testing.assert_array_equal(
                        getattr(ref.classes[c], f),
                        getattr(sh.classes[c], f), err_msg=f"{c}.{f}")
            for ch in ref.channels:
                np.testing.assert_array_equal(
                    ref.channels[ch].link_moves,
                    sh.channels[ch].link_moves)
            assert bool(ref.drained) == bool(sh.drained)
        print("rowshard2 ok")
    """, n_devices=2)


def test_rowshard_validation():
    spec = _mesh_spec()
    with pytest.raises(ValueError, match="positive int"):
        RowShard(0)
    with pytest.raises(ValueError, match="positive int"):
        RowShard(True)
    with pytest.raises(ValueError, match="divisible"):
        simulate(spec, _mixed_wl(), shard=RowShard(3))
    with pytest.raises(ValueError, match="jnp"):
        simulate(spec, _mixed_wl(), shard=RowShard(1), backend="pallas")
    from repro.noc import FaultModel
    faulty = NocSpec.narrow_wide(4, 4, cycles=200,
                                 routing=RoutingPolicy.xy(3),
                                 topology=Torus(4, 4),
                                 faults=FaultModel(dead_links=((1, 2),)))
    with pytest.raises(NotImplementedError):
        simulate(faulty, _mixed_wl(), shard=RowShard(1))


# --------------------------------------------------------------------- #
# satellite: vectorized route-table compile path (byte identity)
# --------------------------------------------------------------------- #
def _reference_expand(policy, topo):
    """The straightforward per-(port, VC) loop expansion the vectorized
    ``routing._compile`` replaced — kept here as the oracle."""
    from repro.noc.routing import _plane_tables
    nbr, opp, _ = topo.tables()
    R, P = nbr.shape
    V, K = policy.n_vcs, policy.n_planes
    v_pp = policy.vcs_per_plane(topo)
    planes, bits = _plane_tables(policy, topo)
    vc_of_hop = np.stack([np.minimum(k * v_pp + b, V - 1)
                          for k, b in enumerate(bits)])
    dest_ids = np.arange(R)
    for k in range(K):
        vc_of_hop[k, dest_ids, dest_ids] = 0
    Pv = (P - 1) * V + 1
    nbr_v = np.full((R, Pv), -1, np.int64)
    opp_v = np.full((R, Pv), Pv - 1, np.int64)
    for p in range(P - 1):
        for v in range(V):
            q = p * V + v
            nbr_v[:, q] = nbr[:, p]
            opp_v[:, q] = np.where(nbr[:, p] >= 0,
                                   opp[:, p] * V + v, Pv - 1)
    route_v = np.full((R, K * R), Pv - 1, np.int64)
    off_diag = dest_ids[:, None] != dest_ids[None, :]
    for k in range(K):
        virt = planes[k] * V + vc_of_hop[k]
        block = route_v[:, k * R:(k + 1) * R]
        block[off_diag] = virt[off_diag]
    return nbr_v, opp_v, route_v, vc_of_hop


@pytest.mark.parametrize("topo,policy", [
    (Mesh(32, 32), RoutingPolicy.xy(2)),
    (Torus(32, 32), RoutingPolicy.xy(2)),
    (Mesh(32, 32), RoutingPolicy.o1turn(2)),
    (Torus(32, 32), RoutingPolicy.o1turn(4)),
    (Mesh(16, 16), RoutingPolicy.valiant(4, 2)),
    (Mesh(16, 16, express=(2, 4)), RoutingPolicy.xy(3)),
], ids=["mesh32_xy2", "torus32_xy2", "mesh32_o1turn", "torus32_o1turn4",
        "mesh16_valiant", "mesh16_express_xy3"])
def test_route_tables_byte_identical_to_reference(topo, policy):
    rt = policy.compile(topo)
    nbr_r, opp_r, route_r, vch_r = _reference_expand(policy, topo)
    for got, ref in ((rt.nbr, nbr_r), (rt.opp, opp_r),
                     (rt.route, route_r), (rt.vc_of_hop, vch_r)):
        assert got.dtype == ref.dtype
        assert got.tobytes() == ref.tobytes()


def test_feeder_tables_byte_identical_to_reference():
    from repro.core.noc_sim.router import feeder_tables
    for topo in (Mesh(32, 32), Torus(32, 32), Mesh(8, 8, express=(2,))):
        nbr, opp, _ = topo.tables()
        R, P = nbr.shape
        src_r = np.full((R, P), -1, np.int64)
        src_o = np.full((R, P), -1, np.int64)
        for t in range(R):
            for o in range(P - 1):
                if nbr[t, o] < 0:
                    continue
                r, p = int(nbr[t, o]), int(opp[t, o])
                assert src_r[r, p] < 0
                src_r[r, p], src_o[r, p] = t, o
        got_r, got_o = feeder_tables(nbr, opp)
        assert got_r.tobytes() == src_r.tobytes()
        assert got_o.tobytes() == src_o.tobytes()


def test_feeder_tables_duplicate_error_message():
    from repro.core.noc_sim.router import feeder_tables
    # router 1's ports 0 and 1 both claim input port 0 of router 0;
    # the t-major first-offender semantics of the old loop must hold
    nbr = np.array([[1, -1, -1], [0, 0, -1]])
    opp = np.array([[0, 2, 2], [0, 0, 2]])
    with pytest.raises(ValueError,
                       match=r"input port 0:0 is fed by two links "
                             r"\(1:0 and 1:1\)"):
        feeder_tables(nbr, opp)


def test_hop_table_analytic():
    n = 8
    h = Torus(n, n).hops()
    exp = np.empty((n * n, n * n), np.int64)
    for s in range(n * n):
        for d in range(n * n):
            dx = abs(s % n - d % n)
            dy = abs(s // n - d // n)
            exp[s, d] = min(dx, n - dx) + min(dy, n - dy)
    np.testing.assert_array_equal(h, exp)
    hm = Mesh(n, n).hops()
    for s, d in ((0, 63), (7, 56), (9, 9)):
        assert hm[s, d] == abs(s % n - d % n) + abs(s // n - d // n)


# --------------------------------------------------------------------- #
# satellite: fused-kernel VMEM budget check
# --------------------------------------------------------------------- #
def test_vmem_budget_raises_with_estimate():
    import jax.numpy as jnp
    from repro.kernels.noc_router import fused_fabric_step_pallas
    N, P, D, F = 4096, 5, 8, 6

    def z(*s):
        return jnp.zeros(s, jnp.int32)

    def operands(n):
        return (z(n, P, D, F), z(n, P), z(n, P), z(n, P, F), z(n, P),
                z(n, P), z(n, P), z(n, P), z(n, P), z(n, P, F),
                jnp.full((n,), D, jnp.int32))

    with pytest.raises(ValueError, match=r"bytes of VMEM .*RowShard"):
        fused_fabric_step_pallas(*operands(N), interpret=False)
    # tightening the budget trips the check on any size; interpret mode
    # never engages it (a small fabric still runs)
    small = operands(8)
    with pytest.raises(ValueError, match="VMEM"):
        fused_fabric_step_pallas(*small, interpret=False,
                                 vmem_budget_bytes=64)
    out = fused_fabric_step_pallas(*small, interpret=True)
    assert out[0].shape == (8, P, D, F)
