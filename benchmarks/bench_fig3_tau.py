"""Figure 3 analog: effect of tau on SGP-SlowMo quality and per-step traffic.

Paper claims: (i) quality has an interior optimum in tau (too-large tau
degrades because workers drift apart); (ii) the averaging cost amortizes as
1/tau, printed here from the analytic communication model."""
from __future__ import annotations

from . import common

TAUS = [3, 12, 48]


def main():
    print("# Fig 3 analog: tau sweep of sgp+slowmo (fixed inner-step budget)")
    print("tau,final_train_loss,eval_loss,comm_bytes_per_step")
    import jax

    from repro.models import param_count

    n = param_count(common.bench_model().init(jax.random.PRNGKey(0)))
    for tau in TAUS:
        cfg = common.preset_cfg("sgp+slowmo", tau=tau)
        r = common.run_algorithm(f"sgp+slowmo_tau{tau}", cfg, cache_key=f"fig3_tau{tau}")
        cb = common.comm_bytes_per_step("sgp+slowmo", n, tau)
        print(f"{tau},{r.final_loss:.4f},{r.eval_loss:.4f},{cb:.0f}")


if __name__ == "__main__":
    main()
