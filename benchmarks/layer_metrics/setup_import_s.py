"""Seconds from the process's start, as the OS has it, to the end of the
program's ``import`` span (``distributed_tpu/__init__.py``, first line to
last): the interpreter, ``jax`` and the runtime's start, which the benchmark
brings up before the package, and the package's own modules
(``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.span_end_s(
        setup, "import")
