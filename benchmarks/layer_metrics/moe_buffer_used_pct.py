"""Share of the expert layers' buffers that the step goes over: tiles in use
over the tiles of the buffers' static worst case, all expert layers, the
whole fit: the program's counters ``tiles_used`` and ``buffer_tiles``
(``nn.DroplessMoE``'s state, read after the fit). The row walks and the
grouped matmuls move that share and no more; a layer that holds every
expert under an even router reads near 100. A program that does not count
its tiles gives None."""


def read(ctx):
    layers = list((ctx.telemetry.get("moe_counters") or {}).values())
    total = sum(c.get("buffer_tiles", 0.0) for c in layers)
    if total <= 0:
        return None
    return 100.0 * sum(c.get("tiles_used", 0.0) for c in layers) / total
