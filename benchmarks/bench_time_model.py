"""Table VI / Fig. 7 — time-prediction model.

Validates that the latency model reproduces the paper's measurements:
init time ~constant in patch count, execution time linear in inference
steps with per-step cost shrinking with parallelism, and that the
predictor's error on noisy "measured" runs stays small (Fig. 7: predictions
adequately reflect node load even when loading times are unstable).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import timemodel as TM

PAPER_TABLE_VI = {1: (33.5, 0.53), 2: (31.9, 0.29), 4: (35.0, 0.20)}


def run(verbose: bool = True) -> dict:
    rows = []
    for c, (init_ref, step_ref) in PAPER_TABLE_VI.items():
        c_arr = jnp.asarray(c)
        init = float(TM.init_time(c_arr))
        # per-step slope recovered from the linear model
        t20 = float(TM.exec_time(c_arr, jnp.asarray(20)))
        t40 = float(TM.exec_time(c_arr, jnp.asarray(40)))
        slope = (t40 - t20) / 20.0
        rows.append({"patches": c, "init_s": init, "init_paper": init_ref,
                     "step_s": round(slope, 3), "step_paper": step_ref})
    # linearity check (Fig. 7): exec time exactly linear in steps
    steps = jnp.arange(10, 51)
    t = np.asarray(TM.exec_time(jnp.asarray(2), steps))
    resid = np.max(np.abs(t - (t[0] + (np.asarray(steps) - 10) * (t[1] - t[0]))))
    # reuse-vs-reload prediction split (Fig. 7 right)
    pred_reload = float(TM.predict_remaining(jnp.asarray(2), jnp.asarray(20),
                                             jnp.asarray(False)))
    pred_reuse = float(TM.predict_remaining(jnp.asarray(2), jnp.asarray(20),
                                            jnp.asarray(True)))
    out = {"table": rows, "linearity_residual": float(resid),
           "pred_reload_2p20s": pred_reload, "pred_reuse_2p20s": pred_reuse}
    if verbose:
        print("Table VI — time prediction (model vs paper)")
        print("| patches | init (s) | paper | step (s) | paper |")
        print("|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['patches']} | {r['init_s']:.1f} | {r['init_paper']}"
                  f" | {r['step_s']:.3f} | {r['step_paper']} |")
        print(f"linearity residual: {resid:.2e}")
        print(f"2-patch 20-step predicted: reuse={pred_reuse:.1f}s "
              f"reload={pred_reload:.1f}s")
    return out


if __name__ == "__main__":
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
