#!/usr/bin/env python
"""Pruning experiment CLI (reference: /root/reference/run_experiment.py).

Usage (reference README.md:84-92 equivalent):
    python run_experiment.py --config-name=cifar10_imp \
        experiment_params.epochs_per_level=10 optimizer_params.lr=0.1

Config groups compose Hydra-style from conf/ (see
turboprune_tpu/config/compose.py). Multi-host TPU runs launch the SAME
command on every host; jax.distributed is initialized automatically when a
multi-host environment is detected.
"""

from __future__ import annotations

import argparse
import sys
import time


def parse_args(argv):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--config-name",
        required=True,
        help="top-level config under conf/ (e.g. cifar10_imp)",
    )
    parser.add_argument(
        "--config-path", default=None, help="alternate config root directory"
    )
    parser.add_argument(
        "overrides",
        nargs="*",
        help="dotted overrides like optimizer_params.lr=0.05",
    )
    return parser.parse_args(argv)


def setup(args):
    """What both entry points do before the level loop, each part under its
    ``setup/`` span: the package imports, the compile cache's place and the
    composed config, distributed initialisation, the start of the backend's
    runtime (seconds on a TPU, which would otherwise fall into whichever
    span first asks for a device). Returns the config."""
    t0 = time.perf_counter()
    from turboprune_tpu.utils import tracing

    with tracing.span("setup/imports") as imports:
        from turboprune_tpu import driver  # noqa: F401  (pulls in the rest)
        from turboprune_tpu.config.compose import compose
        from turboprune_tpu.parallel import initialize_distributed
        from turboprune_tpu.utils.compile_cache import place_compile_cache
    imports.start = t0  # the recorder's own import came first, and is most of it

    with tracing.span("setup/config"):
        place_compile_cache()
        cfg = compose(args.config_name, args.overrides, args.config_path)
    with tracing.span("setup/distributed"):
        initialize_distributed()
    with tracing.span("setup/backend"):
        import jax

        jax.devices()
    return cfg


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    cfg = setup(args)

    from turboprune_tpu.driver import run
    from turboprune_tpu.parallel import is_primary

    expt_dir, summaries = run(cfg)
    if is_primary():
        print(f"\nExperiment complete: {expt_dir}")
        for s in summaries:
            print(
                f"  level {s['level']}: density {s['density']:.4f} "
                f"max_test_acc {s.get('max_test_acc', float('nan')):.2f}%"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
