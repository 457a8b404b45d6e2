"""The paper's core experiment (Tables II/IV): FedAvg vs T-FedAvg on the
synthetic MNIST stand-in, with accuracy + communication measured from the
real serialized wire buffers, plus simulated transfer times from the
channel model. ``--mode async`` runs the buffered-asynchronous server.

    PYTHONPATH=src python examples/federated_training.py [--rounds 10]
    PYTHONPATH=src python examples/federated_training.py --noniid 2
    PYTHONPATH=src python examples/federated_training.py --mode async --buffer-k 3
    PYTHONPATH=src python examples/federated_training.py --deadline 0.3
    PYTHONPATH=src python examples/federated_training.py --mode async \\
        --availability diurnal --loss-rate 0.01 --max-staleness 4
"""

import argparse

import jax
import jax.numpy as jnp

from repro.comm import ChannelConfig
from repro.core import FTTQConfig
from repro.data import (
    partition_iid, partition_noniid, synthetic_classification,
)
from repro.fed import AvailabilityConfig, FedConfig, run_federated
from repro.launch.env import configure_compile_cache
from repro.models.paper_models import init_mlp_mnist, mlp_mnist
from repro.optim import adam


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--noniid", type=int, default=0,
                    help="classes per client (0 = IID)")
    ap.add_argument("--mode", choices=("sync", "async"), default="sync")
    ap.add_argument("--buffer-k", type=int, default=4,
                    help="async: aggregate every K arrivals")
    ap.add_argument("--bandwidth-mbps", type=float, default=8.0,
                    help="median link bandwidth, megabits/s")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="sync-only round deadline in seconds (0 = none); "
                         "slow clients become emergent stragglers. The async "
                         "server has no barrier, so no deadline applies.")
    # --- scenario layer ---------------------------------------------------
    ap.add_argument("--availability", choices=("always_on", "diurnal", "trace"),
                    default="always_on",
                    help="client availability trace (diurnal = sinusoidal "
                         "timezone cohorts, trace = seeded on/off sessions)")
    ap.add_argument("--loss-rate", type=float, default=0.0,
                    help="per-chunk packet loss probability; lost chunks "
                         "retransmit with timeout backoff and are metered")
    ap.add_argument("--max-staleness", type=int, default=0,
                    help="async: drop updates staler than this (0 = no cap)")
    ap.add_argument("--adaptive-buffer", action="store_true",
                    help="async: auto-tune buffer_k from the arrival rate")
    args = ap.parse_args()
    configure_compile_cache()
    if args.mode == "async" and args.deadline > 0:
        ap.error("--deadline applies to --mode sync only "
                 "(the async server never blocks on a round barrier)")

    x, y, xt, yt = synthetic_classification(
        jax.random.PRNGKey(0), 4000, 10, 784, noise=3.0, n_test=1000)
    if args.noniid:
        clients = partition_noniid(x, y, args.clients, args.noniid)
    else:
        clients = partition_iid(x, y, args.clients)
    params = init_mlp_mnist(jax.random.PRNGKey(1))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)

    def eval_fn(p):
        logits = mlp_mnist(p, xt_j)
        acc = jnp.mean(jnp.argmax(logits, -1) == yt_j)
        logp = jax.nn.log_softmax(logits, -1)
        return float(acc), float(-jnp.mean(
            jnp.take_along_axis(logp, yt_j[:, None], -1)))

    chan = ChannelConfig(
        mean_bandwidth_bytes_s=args.bandwidth_mbps * 1e6 / 8,
        deadline_s=args.deadline if args.deadline > 0 else float("inf"),
        loss_rate=args.loss_rate,
    )
    avail = AvailabilityConfig(kind=args.availability)
    print(f"{'algo':10s} {'acc':>7s} {'upload':>10s} {'download':>10s} "
          f"{'sim-time':>9s} {'p95-xfer':>9s}")
    results = {}
    for algo in ("fedavg", "tfedavg"):
        cfg = FedConfig(algorithm=algo, mode=args.mode,
                        participation=args.participation,
                        local_epochs=2, batch_size=32, rounds=args.rounds,
                        fttq=FTTQConfig(), channel=chan,
                        buffer_k=args.buffer_k, availability=avail,
                        max_staleness=args.max_staleness,
                        adaptive_buffer=args.adaptive_buffer)
        res = run_federated(mlp_mnist, params, clients, cfg, adam(1e-3),
                            eval_fn, eval_every=args.rounds)
        results[algo] = res
        print(f"{algo:10s} {res.accuracy[-1]:7.3f} "
              f"{res.upload_bytes / 1e6:9.2f}M {res.download_bytes / 1e6:9.2f}M "
              f"{res.total_time_s:8.2f}s "
              f"{res.transfer_summary['p95_seconds'] * 1e3:7.1f}ms")
        if res.dropped_per_round and sum(res.dropped_per_round):
            print(f"{'':10s} stragglers dropped per round: "
                  f"{res.dropped_per_round}")
        tel = res.telemetry
        if tel.get("retrans_bytes") or tel.get("dropped_updates"):
            # sync drops stragglers at the deadline; async drops over-stale
            # arrivals whose bytes were already paid for.
            what = "stale" if args.mode == "async" else "straggler"
            print(f"{'':10s} scenario: retrans "
                  f"{tel.get('retrans_bytes', 0) / 1e3:.1f}kB "
                  f"(goodput {tel.get('goodput_fraction', 1.0):.3f}), "
                  f"{what}-dropped {tel.get('dropped_updates', 0)} "
                  f"({tel.get('dropped_update_bytes', 0) / 1e3:.1f}kB wasted)")
        if args.adaptive_buffer and tel.get("buffer_k_per_agg"):
            print(f"{'':10s} buffer_k trajectory: {tel['buffer_k_per_agg']}")
    r = results["fedavg"].upload_bytes / results["tfedavg"].upload_bytes
    t = results["fedavg"].total_time_s / max(results["tfedavg"].total_time_s, 1e-9)
    print(f"\ncommunication compression: {r:.1f}×  wall-clock speedup: {t:.1f}×  "
          f"(paper Table IV reports ~16×; biases stay fp32, framing adds bytes)")


if __name__ == "__main__":
    main()
