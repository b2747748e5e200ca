"""Robustness demo — the alie-vs-DTS-v3 showdown.

Headline scenario: k=4 ALIE colluders ("a little is enough", Baruch et
al.) join 12 vanilla workers on a non-iid partition. Every colluder
ships the IDENTICAL ``mean − z·std`` of the worker stack — a coordinated
shift hiding inside the empirical variance, stealthy to the paper's
loss-delta trust AND to single-round update geometry. The one thing the
colluders cannot avoid is each other: across rounds their payloads
correlate at ≈ 1 while non-iid honest updates decorrelate, and that is
exactly what ``--dts-signal all`` (loss + geometry + the DTS v3
cross-round sketch-correlation channel) scores.

The demo runs the SAME scenario twice — paper DTS (``"loss"``) vs the
fused v3 signal (``"all"``) — and prints the ASCII trust matrix at a few
horizons so you can watch one defense stay blind while the other freezes
the colluder block out. A straggler runs throughout (the sketch ring
buffer must not rotate on rounds a worker never ran — frozen rows, not
phantom history). Everything replays inside the fused scanned superstep:
each run is ONE XLA dispatch, sketches included.

    PYTHONPATH=src python examples/robustness_demo.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import enable_compile_cache
from repro.config import DeFTAConfig, TrainConfig
from repro.core import dts
from repro.core.defta import evaluate, run_defta
from repro.core.tasks import mlp_task
from repro.data.synthetic import federated_dataset
from repro.scenarios import (AttackSpec, ScenarioSpec, StragglerSpec,
                             compile_scenario)

VANILLA, COLLUDERS, EPOCHS = 12, 4, 24

SCENARIO = ScenarioSpec(
    name="alie_showdown",
    attacks=tuple(AttackSpec("alie") for _ in range(COLLUDERS)),
    stragglers=(StragglerSpec(worker=5, speed=0.5),),
)


def trust_picture(theta, adj, malicious, alive):
    chars = " .:-=+*#%@"
    lines = []
    for i in range(len(theta)):
        row = []
        for j in range(len(theta)):
            if not adj[i, j]:
                row.append(" ")
            else:
                row.append(chars[min(int(theta[i, j] * 3 * 9), 9)])
        mark = "M" if malicious[i] else ("x" if not alive[i] else " ")
        lines.append(f"  {i:2d}{mark} |" + "".join(row) + "|")
    head = "       " + "".join(
        "M" if malicious[j] else str(j % 10) for j in range(len(theta)))
    return head + "\n" + "\n".join(lines) + "\n  (M=malicious, x=left)"


def attacker_share(theta, adj, malicious):
    t = np.asarray(theta)
    return float(t[~malicious][:, malicious].sum(axis=1).mean())


def main():
    rng = np.random.default_rng(0)
    data = federated_dataset("vector", VANILLA, rng, n_per_worker=120,
                             alpha=0.5)                        # non-iid
    task = mlp_task(32, 10)
    train = TrainConfig(learning_rate=0.05, batch_size=32)

    compiled = compile_scenario(SCENARIO, VANILLA, EPOCHS)
    print(f"scenario: {compiled.summary()}")

    final = {}
    for signal in ("loss", "all"):
        cfg = DeFTAConfig(num_workers=VANILLA, avg_peers=4, num_sampled=2,
                          local_epochs=3, dts_signal=signal)
        print(f"\n{'=' * 66}\n--dts-signal {signal}"
              + ("  (paper DTS: scalar loss delta)" if signal == "loss"
                 else "  (DTS v3 fusion: loss + geometry + cross-round "
                      "correlation)"))
        # snapshot θ at two horizons by re-running from scratch to each —
        # runs are deterministic (same key), so the epoch-8 state inside
        # the 24-epoch run IS the 8-epoch run's state; each replay is
        # still ONE fused superstep dispatch (cheap at demo scale)
        stats = {}
        for upto in (8, EPOCHS):
            st, adj, malicious, _ = run_defta(
                jax.random.PRNGKey(0), task, cfg, train, data,
                epochs=upto, scenario=compiled, stats=stats)
            theta = np.asarray(dts.sample_weights(st.conf,
                                                  jnp.asarray(adj)))
            alive = compiled.alive_np[compiled.seg_of_epoch_np[upto - 1]]
            print(f"\n  epoch {upto}: sampling weights θ (rows=receiver, "
                  f"cols=sender) — {stats['dispatches']} dispatch(es), "
                  f"attacker-θ share {attacker_share(theta, adj, malicious):.3f}")
            print(trust_picture(theta, adj, malicious, alive))
        if st.sketch is not None:
            r = int((np.abs(np.asarray(st.sketch)).max(axis=2) > 0).sum(1).max())
            print(f"  sketch ring buffer: {tuple(st.sketch.shape)}, "
                  f"{r}/{st.sketch.shape[1]} rounds of history filled")
        m, s, _ = evaluate(task, st, data["test_x"], data["test_y"],
                           malicious)
        final[signal] = (m, attacker_share(theta, adj, malicious))
        print(f"  final honest accuracy: {m:.3f} ± {s:.3f}")

    (acc_l, th_l), (acc_a, th_a) = final["loss"], final["all"]
    print(f"\n{'=' * 66}\nshowdown: loss {acc_l:.3f} (attacker-θ {th_l:.3f})"
          f"  vs  all {acc_a:.3f} (attacker-θ {th_a:.3f})"
          f"  ->  +{acc_a - acc_l:.3f} honest accuracy from the "
          f"correlation channel")


if __name__ == "__main__":
    enable_compile_cache()
    main()
