"""Standalone shard host: one TCP server owning a private
``SubsetEvaluationCore``, speaking the serving plane's op contract.

Start H of these (one per box / per core pool), then point the serving
front at them:

  PYTHONPATH=src python -m repro.launch.shard_host --port 9701 &
  PYTHONPATH=src python -m repro.launch.shard_host --port 9702 &
  PYTHONPATH=src python -m repro.launch.serve --federation --async \
      --transport socket --hosts 127.0.0.1:9701,127.0.0.1:9702

Every host must be started with the SAME roster arguments
(``--images``/``--seed``/ensemble config) as the front: the client's
connect-time ``hello`` handshake refuses hosts whose trace fingerprints
or config differ, because such hosts would answer valid-but-different
rows and silently break cross-shard bit-parity.  See ``docs/serving.md``.

A host is host-only: it pins jax to the CPU and uses the numpy IoU twin,
so it never reaches for a chip that the serving front holds.
"""
from __future__ import annotations

import argparse
import socket


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", default="127.0.0.1",
                    help="interface to bind (default 127.0.0.1)")
    ap.add_argument("--port", type=int, default=0,
                    help="TCP port (0 = ephemeral, printed on start)")
    ap.add_argument("--images", type=int, default=64,
                    help="roster size; must match the serving front")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace seed; must match the serving front")
    ap.add_argument("--voting", default="affirmative")
    ap.add_argument("--ablation", default="wbf")
    ap.add_argument("--iou-thr", type=float, default=0.5)
    args = ap.parse_args(argv)

    from repro.federation.providers import default_providers
    from repro.federation.traces import generate_traces
    from repro.launch.compile_cache import configure_compile_cache
    from repro.serving.mp_shards import pin_host_only, shard_cfg
    from repro.serving.socket_shards import serve_host

    pin_host_only()
    configure_compile_cache()
    cfg = shard_cfg(args.voting, args.ablation, args.iou_thr)
    traces = generate_traces(default_providers(), args.images,
                             seed=args.seed)

    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((args.host, args.port))
    srv.listen(16)
    print(f"[shard_host] serving {traces.n_providers} providers / "
          f"{args.images} images on {args.host}:{srv.getsockname()[1]} "
          f"(cfg={cfg})", flush=True)
    try:
        serve_host(srv, traces, cfg)
    except KeyboardInterrupt:
        pass
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
