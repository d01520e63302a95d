"""``rbg-tpu`` CLI — the kubectl-plugin equivalent of the reference
(``cmd/cli/root.go:38-45``: status / rollout history|diff|undo).

Subcommands grow with the control plane; ``version`` and ``presets`` are
always available.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # Pass-through subcommands with their own parsers (argparse subparsers
    # don't reliably forward option-like REMAINDER args).
    if argv and argv[0] == "stress":
        from rbg_tpu.stress.harness import main as stress_main
        return stress_main(argv[1:])
    if argv and argv[0] == "deploy-manifests":
        from rbg_tpu.cli.deploygen import run as deploygen_run
        return deploygen_run(argv[1:])
    if argv and argv[0] == "lint":
        from rbg_tpu.analysis.cli import run as lint_run
        return lint_run(argv[1:])
    if argv and argv[0] == "top":
        from rbg_tpu.cli.top import run as top_run
        return top_run(argv[1:])

    parser = argparse.ArgumentParser(
        prog="rbg-tpu",
        description="TPU-native role-based group orchestration + serving",
    )
    sub = parser.add_subparsers(dest="cmd")
    sub.add_parser("version", help="print version")
    sub.add_parser("presets", help="list model presets")
    register_extra_commands(sub)

    args = parser.parse_args(argv)
    if args.cmd == "version":
        import rbg_tpu
        print(rbg_tpu.__version__)
        return 0
    if args.cmd == "presets":
        from rbg_tpu.models import list_presets
        for p in list_presets():
            print(p)
        return 0
    if hasattr(args, "func"):
        return args.func(args)
    parser.print_help()
    return 1


def register_extra_commands(sub) -> None:
    """Control-plane commands (apply/status/rollout) register here; kept in a
    separate hook so the data plane imports stay lazy."""
    try:
        from rbg_tpu.cli import controlplane
    except ImportError:
        return
    controlplane.register(sub)


if __name__ == "__main__":
    sys.exit(main())
