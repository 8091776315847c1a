"""Delay-aware tuning demo (paper §6): for a range of link delays, let
``Schedule(rounds="auto")`` pick the eq.-(12)-optimal local iteration count
H from the topology's delay model, and verify it against actual simulated
runs of CoCoA (star sessions) on ridge regression.

    PYTHONPATH=src python examples/ridge_delay_sweep.py
"""
from repro.api import Problem, Schedule, Session, Topology
from repro.compat import enable_compile_cache
from repro.core.delay import optimal_h
from repro.core.dual import duality_gap
from repro.data.synthetic import gaussian_regression

T_LP, T_CP, LAM, K = 4e-5, 3e-5, 1e-2, 3
BUDGET = 2.0  # seconds of simulated wall-clock


def main():
    enable_compile_cache()
    X, y = gaussian_regression(m=600, d=100)
    m = X.shape[0]
    problem = Problem.ridge(X, y, lam=LAM)

    print(f"{'r':>10} {'H* (auto)':>12} {'best H (sim)':>14} "
          f"{'gap @ H*':>12}")
    for r in (1.0, 100.0, 1e4):
        t_delay = r * T_LP
        topo = Topology.star(K, m // K, t_lp=T_LP, t_cp=T_CP,
                             t_delay=t_delay)

        # the session's auto schedule runs eq. (12) at compile time
        auto = Session.compile(
            problem, topo,
            Schedule.auto(t_total=BUDGET, C=0.5, delta=1 / (m // K),
                          t_cp=T_CP, h_max=10**6))
        h_star = auto.resolved.chunk_tree.leaves()[0].rounds
        h_ref, _ = optimal_h(C=0.5, K=K, delta=1 / (m // K), t_total=BUDGET,
                             t_lp=T_LP, t_delay=t_delay, t_cp=T_CP,
                             h_max=10**6)
        assert h_star == h_ref, (h_star, h_ref)

        # simulate a small grid around H* -- one vectorized sweep over the
        # schedule axis -- and report the empirical best
        hs = sorted({max(h_star // 8, 1), max(h_star // 2, 1), h_star,
                     h_star * 2, h_star * 8})
        scheds = [
            Schedule(rounds=min(max(int(
                BUDGET / (T_LP * H + t_delay + T_CP)), 1), 2000),
                local_steps=H)
            for H in hs
        ]
        rs = auto.sweep(schedules=scheds, record_history=False)
        gaps = {
            H: float(duality_gap(res.alpha, X, y, problem.loss, LAM))
            for H, res in zip(hs, rs, strict=True)
        }
        best = min(gaps, key=gaps.get)
        print(f"{r:>10.0f} {h_star:>12d} {best:>14d} {gaps[h_star]:>12.3e}")
        # the eq.-(12) pick is within ~4x of the empirical best
        assert best / 8 <= h_star <= best * 8, (r, h_star, best)

    print("\n(the analytic H* tracks the empirically-best H across delay "
          "regimes)")


if __name__ == "__main__":
    main()
