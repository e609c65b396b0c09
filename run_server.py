#!/usr/bin/env python
"""Serve pruned TurboPrune-TPU checkpoints over HTTP.

Usage:
    python run_server.py --expt-dir experiments/<dir> [serve.port=8080 ...]
    python run_server.py serve.expt_dir=experiments/<dir> serve.checkpoint_level=3
    python run_server.py --config-name serve serve=fleet \
        "serve.fleet.expt_dirs=[experiments/<dir>]"   # every level, one process

The serve group composes Hydra-style from conf/serve/ (see conf/serve.yaml);
the model architecture and input geometry come from the experiment dir's own
expt_config.yaml snapshot, so the served checkpoint always matches its model.

Endpoints:
    POST /predict   {"instances": [[H][W][C] floats, ...], "model": "level_3"}
                    ("model" routes within a fleet; omit for the default)
    GET  /healthz   checkpoint level/density, buckets, queue depth
                    (fleet: one row per registered model)
    GET  /metrics   Prometheus text (latency histogram, throughput,
                    queue depth, compile/AOT-cache hit/miss; fleet series
                    are labelled by model id)

SIGTERM triggers a graceful shutdown: the listener stops, already-accepted
requests are answered for up to serve.drain_timeout_s, then the process
exits — a rolling restart drops nothing it had accepted.
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def parse_args(argv):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--config-name",
        default="serve",
        help="top-level config under conf/ (default: serve)",
    )
    parser.add_argument(
        "--config-path", default=None, help="alternate config root directory"
    )
    parser.add_argument(
        "--expt-dir",
        default="",
        help="experiment directory to serve (overrides serve.expt_dir)",
    )
    parser.add_argument(
        "overrides",
        nargs="*",
        help="dotted overrides like serve.port=8080 serve.max_batch=64",
    )
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])

    from turboprune_tpu.config.compose import compose
    from turboprune_tpu.serve import NotServable, build_server
    from turboprune_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    cfg = compose(args.config_name, args.overrides, args.config_path)
    try:
        server = build_server(cfg, expt_dir=args.expt_dir)
    except NotServable as e:
        print(f"run_server: {e}", file=sys.stderr, flush=True)
        return 2
    host, port = server.server_address[:2]
    if server.fleet is not None:
        info = server.fleet.info()
        models = ", ".join(sorted(info["models"]))
        print(
            f"serving fleet of {len(info['models'])} models "
            f"(default={info['default_model']}, "
            f"resident<={info['max_resident_models']})\n"
            f"  models: {models}\n"
            f"  POST http://{host}:{port}/predict "
            f'{{"instances": ..., "model": "<id>"}}   '
            f"GET /healthz   GET /metrics",
            flush=True,
        )
    else:
        info = server.engine.info()
        print(
            f"serving {info['source']}\n"
            f"  level={info['level']} density={info['density']} "
            f"buckets={info['buckets']} "
            f"compiled={info['compiled_buckets']}\n"
            f"  POST http://{host}:{port}/predict   "
            f"GET /healthz   GET /metrics",
            flush=True,
        )

    def _on_sigterm(signum, frame):
        # shutdown() handshakes with the serve_forever loop running on THIS
        # (main) thread — calling it inline here would deadlock, so the
        # drain runs on its own thread while serve_forever unwinds below.
        print("\nSIGTERM: draining in-flight requests", flush=True)
        threading.Thread(
            target=server.graceful_shutdown,
            name="turboprune-drain",
            daemon=True,
        ).start()

    signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        print("\nshutting down", flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
