"""M_L server process: the out-of-process half of the distributed tier.

Owns the large `ModelRunner` and serves batched regeneration over the
socket RPC protocol in `repro.serving.remote`. The serving engine
connects with ``--large-backend socket --ml-address host:port`` (or
``--large-backend pool`` across several of these); greedy parity across
processes holds because `build_runners(arch, seed, widths)` derives the
large model's parameters deterministically from ``--arch``/``--seed``/
``--widths`` — run the server and the engine with the same values. On a
host with one TPU chip only one process can hold the chip, so there the
M_L tier stays in the engine's process (``--large-backend sync``).

    # one replica on a fixed port
    PYTHONPATH=src python -m repro.launch.ml_server --port 7070

    # the engine, in another shell
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --large-backend socket --ml-address 127.0.0.1:7070

Batching policy (--large-batch / --max-wait) lives server-side: the
server owns the `BatchPolicy`, so batch shapes — and therefore padding
behavior — are decided where the compute runs. Ctrl-C (or a client
``shutdown`` frame) stops the server after the current batch.
"""
from __future__ import annotations

import argparse
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import WIDTHS, build_runners
from repro.serving.remote import MLServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    help="architecture preset; must match the engine's "
                         "--arch for cross-process greedy parity")
    ap.add_argument("--seed", type=int, default=0,
                    help="parameter seed; must match the engine's --seed")
    ap.add_argument("--widths", choices=WIDTHS, default="reduced",
                    help="model ladder widths; must match the engine's "
                         "--widths")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="listen port (0 = ephemeral, printed at startup)")
    ap.add_argument("--max-new", type=int, default=8,
                    help="regeneration length; must match the engine's "
                         "--max-new")
    ap.add_argument("--large-batch", type=int, default=0,
                    help="regeneration batch size (0 = one exact-size "
                         "batch at drain)")
    ap.add_argument("--max-wait", type=float, default=0.0,
                    help="seconds a partial batch may wait before "
                         "flushing padded (0 = wait for a full batch)")
    ap.add_argument("--latency", type=float, default=0.0,
                    help="injected per-batch response delay (benches)")
    args = ap.parse_args()

    enable_compile_cache()
    _small, large, _cfg = build_runners(args.arch, args.seed, args.widths)
    srv = MLServer(large, max_new=args.max_new,
                   large_batch=args.large_batch or None,
                   max_wait=args.max_wait or None,
                   host=args.host, port=args.port,
                   latency=args.latency).start()
    host, port = srv.address
    print(f"M_L server ({args.arch}, seed {args.seed}) listening on "
          f"{host}:{port} — connect with --large-backend socket "
          f"--ml-address {host}:{port}", flush=True)
    try:
        while srv.running:
            time.sleep(0.2)
        print("shutdown frame received, stopping")
    except KeyboardInterrupt:
        print("interrupted, stopping")
    finally:
        srv.stop()
    print(f"served {len(srv.batch_log)} batches")


if __name__ == "__main__":
    main()
