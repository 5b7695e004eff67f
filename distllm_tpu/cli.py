"""Command-line interface for distllm-tpu.

Parity target: the reference's typer CLI (``distllm/cli.py``, console script
``distllm``) with subcommands ``embed``, ``merge``, ``generate``, ``tokenize``
and ``chunk_fasta_file``. ``typer`` is not available in this environment, so
the CLI is plain argparse; subcommands are registered lazily so importing the
CLI stays cheap.
"""

from __future__ import annotations

import argparse
import sys
from typing import Callable

_SUBCOMMANDS: dict[str, Callable[[argparse.ArgumentParser], None]] = {}
_RUNNERS: dict[str, Callable[[argparse.Namespace], int | None]] = {}


def subcommand(name: str, help_text: str = ''):
    """Register a CLI subcommand: decorate a (parser-setup, runner) pair."""

    def deco(setup: Callable[[argparse.ArgumentParser], Callable]):
        def register_parser(sub: argparse.ArgumentParser) -> None:
            runner = setup(sub)
            _RUNNERS[name] = runner

        register_parser.help_text = help_text
        _SUBCOMMANDS[name] = register_parser
        return setup

    return deco


def _build_parser() -> argparse.ArgumentParser:
    # Import modules that register subcommands (lazy heavy deps inside).
    from distllm_tpu import cli_commands  # noqa: F401

    parser = argparse.ArgumentParser(
        prog='distllm-tpu',
        description='TPU-native distributed LLM inference toolkit.',
    )
    subparsers = parser.add_subparsers(dest='command')
    for name, register_parser in sorted(_SUBCOMMANDS.items()):
        sub = subparsers.add_parser(
            name, help=getattr(register_parser, 'help_text', '')
        )
        register_parser(sub)
    return parser


def main(argv: list[str] | None = None) -> int:
    from distllm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    parser = _build_parser()
    args = parser.parse_args(argv)
    if not args.command:
        parser.print_help()
        return 2
    result = _RUNNERS[args.command](args)
    return int(result or 0)


if __name__ == '__main__':
    # Under `python -m distllm_tpu.cli` this file runs as `__main__`; delegate
    # to the canonical module so subcommands register into the same tables.
    from distllm_tpu.cli import main as _canonical_main

    sys.exit(_canonical_main())
