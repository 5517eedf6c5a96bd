"""Mean share of the KV pool's blocks in use over the decode steps
(``last_run_telemetry["kv_utilization"]["mean"]``)."""


def read(ctx):
    util = ctx.telemetry.get("kv_utilization")
    return None if not util else 100.0 * util["mean"]
