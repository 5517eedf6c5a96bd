"""Wall of the first calls that compile or load the cell's programs: the
first fit step less a steady step (train), the warm-up pass over every
prefill bucket and the decode program (serve)."""


def read(ctx):
    return ctx.telemetry.get("setup_compile_s")
