"""Time model: Table-1 feature families, OLS fit, serialisation."""
import numpy as np
import pytest

from repro.core.graph import Task, TaskKind, TileRef
from repro.core.machine import ClusterSpec, c5_9xlarge
from repro.core.profiler import (profile_comm_synthetic, profile_machine,
                                 profile_matmul)
from repro.core.timemodel import (PolyModel, TimeModel, analytic_time_model,
                                  features_ewise, features_matmul)


def test_feature_vectors():
    np.testing.assert_array_equal(features_ewise((3, 4)), [1, 4, 3, 12])
    np.testing.assert_array_equal(
        features_matmul((2, 3, 4)), [1, 2, 3, 4, 6, 12, 8, 24])


def test_ols_recovers_synthetic_coefficients():
    rng = np.random.default_rng(0)
    true = np.array([1e-4, 0, 0, 0, 0, 0, 0, 2e-10])
    dims = [(m, n, k) for m in (64, 128, 256) for n in (64, 128, 256)
            for k in (64, 128, 256)]
    times = [features_matmul(d) @ true * (1 + 0.01 * rng.standard_normal())
             for d in dims]
    model = PolyModel.fit("matmul", dims, times)
    assert model.r2(dims, times) > 0.99
    pred = model.predict((512, 512, 512))
    want = features_matmul((512, 512, 512)) @ true
    assert abs(pred - want) / want < 0.1


@pytest.mark.parametrize("seed", [0, 6, 9])
def test_fit_on_loaded_timings_stays_positive_and_grows(seed):
    """Timings from a loaded host, each inflated 1-30x at random, on the
    profiler's grid with its mat-vec tiles.  Plain OLS prices a tile
    outside the grid at <= 0 here (a 384^3 GEMM tile for seed 6, a 384^2
    mat-vec for seeds 0 and 9).  The fit holds every coefficient
    non-negative, so it prices both above zero, the mat-vec below the
    GEMM, and the larger GEMM above the smaller one."""
    rng = np.random.default_rng(seed)
    true = np.array([2e-5, 0, 0, 0, 0, 0, 0, 5e-11])
    sizes = (64, 128, 256)
    dims = [(m, m, k) for m in sizes for k in (*sizes, 1)]
    times = [features_matmul(d) @ true * rng.uniform(1, 30) for d in dims]
    X = np.stack([features_matmul(d) for d in dims])
    ols, *_ = np.linalg.lstsq(X, np.array(times), rcond=None)
    mm, mv = (384, 384, 384), (384, 384, 1)
    assert min(features_matmul(mm) @ ols, features_matmul(mv) @ ols) <= 0
    model = PolyModel.fit("matmul", dims, times)
    assert (model.coef >= 0).all()
    assert 1e-9 < model.predict(mv) < model.predict(mm)
    assert model.predict((256, 256, 256)) < model.predict(mm)


def test_profile_matmul_times_mat_vec_tiles():
    dims, times = profile_matmul((16, 32), reps=1)
    assert dims == [(16, 16, 16), (16, 16, 32), (16, 16, 1),
                    (32, 32, 16), (32, 32, 32), (32, 32, 1)]
    assert len(times) == len(dims) and min(times) > 0


def test_profile_machine_fits_reasonably():
    tm = profile_machine(sizes=(64, 128, 192), reps=1)
    t_small = tm.models["matmul"].predict((64, 64, 64))
    t_big = tm.models["matmul"].predict((192, 192, 192))
    assert t_big > t_small > 0


def test_serialisation_roundtrip(tmp_path):
    tm = analytic_time_model()
    p = tmp_path / "tm.json"
    tm.save(str(p))
    tm2 = TimeModel.load(str(p))
    task = Task(0, TaskKind.ADDMUL,
                (TileRef(0, 0, 0, (64, 64)), TileRef(1, 0, 0, (64, 64))),
                TileRef(2, 0, 0, (64, 64)))
    assert tm.compute_time(task) == pytest.approx(tm2.compute_time(task))


def test_comm_model_per_pair():
    spec = ClusterSpec(n_nodes=3, pair_bw=(((0, 1), 1e9), ((1, 2), 2e9)))
    assert spec.comm_time(1e9, 0, 1) > spec.comm_time(1e9, 1, 2)
    assert spec.comm_time(123, 1, 1) == 0.0


def test_comm_profile_synthetic_fit():
    spec = c5_9xlarge(3)
    fitted = profile_comm_synthetic(spec, noise=0.01)
    lat, bw = fitted[(0, 1)]
    assert abs(bw - spec.link_bw) / spec.link_bw < 0.2
    assert lat < 10 * spec.latency


def test_straggler_slowdown():
    spec = ClusterSpec(n_nodes=2, slowdown=(1.0, 2.0))
    tm = analytic_time_model()
    task = Task(0, TaskKind.ADDMUL,
                (TileRef(0, 0, 0, (256, 256)), TileRef(1, 0, 0, (256, 256))),
                TileRef(2, 0, 0, (256, 256)))
    assert tm.compute_time(task, spec, 1) == pytest.approx(
        2 * tm.compute_time(task, spec, 0))
