"""Programs that the persistent compile cache was asked for and did not
have, in the set-up window: the compile ledger's ``backend`` records whose
``cache`` reads ``miss``, of the programs that a span of the program asked
for (the records themselves, no counter). 0 on a warm run; a "warm" reading that shows 1
explains itself, and the timeline's dump names the program
(``benchmarks/setup_timeline.py``)."""

from benchmarks import setup_timeline


def read(ctx):
    setup = setup_timeline.read_setup(ctx)
    return None if setup is None else setup_timeline.cache_misses(setup)
