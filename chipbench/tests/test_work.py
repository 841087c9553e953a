"""The FLOP and byte counts of both configurations against hand counts."""
import cell

BENCH = cell.benchmark()


def test_logreg_work_hand_count():
    cfg, mod = cell.config(BENCH, "nuts-logreg-10k")
    w = mod.work(cfg, 1024)
    # X w and X^T r: 2 * 10,000 * 100 multiply-adds each, per chain.
    assert w["grad_flops"] == 4 * 10_000 * 100 * 1024 == 4_096_000_000
    assert w["value_flops"] == 2 * 10_000 * 100 * 1024
    # X once (float32), weights in and gradients out.
    assert w["grad_bytes"] == 4_000_000 + 2 * 400 * 1024
    assert w["value_bytes"] == 4_000_000 + 400 * 1024 + 4 * 1024


def test_corrgauss_work_hand_count():
    cfg, mod = cell.config(BENCH, "nuts-corrgauss-100")
    # D = 3 by hand.  Px: rows 2, 3, 2 multiplies; 1, 2, 1 adds -> 11.
    # x'Px/2: x*x*main 6 mul, sum 2 adds; off*x[:-1]*x[1:] 4 mul, sum 1
    # add; 2.0*, +, -0.5* -> 16.
    small = {**cfg, "dim": 3}
    w = mod.work(small, 1)
    assert (w["grad_flops"], w["value_flops"]) == (11, 16)
    assert w["grad_bytes"] == 4 * 5 + 2 * 4 * 3
    w = mod.work(cfg, 1024)
    assert w["grad_flops"] == (5 * 100 - 4) * 1024
    assert w["value_flops"] == (6 * 100 - 2) * 1024


def test_corrgauss_reference_gradient_is_minus_px():
    import numpy as np

    cfg, mod = cell.config(BENCH, "nuts-corrgauss-100")
    main, off = mod.precision(cfg)
    p = np.diag(main.astype(np.float64)) + np.diag(off, 1) + np.diag(off, -1)
    # The precision matrix inverts the AR(1) covariance rho^|i-j|.
    idx = np.arange(cfg["dim"])
    cov = cfg["rho"] ** np.abs(idx[:, None] - idx[None, :])
    np.testing.assert_allclose(p @ cov, np.eye(cfg["dim"]), atol=1e-4)
    x = np.random.default_rng(0).normal(size=cfg["dim"]).astype(np.float32)
    logp, grad = mod.reference(cfg)
    np.testing.assert_allclose(np.asarray(grad(x)), -p @ x, rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(float(logp(x)), -0.5 * x @ p @ x, rtol=1e-5)
