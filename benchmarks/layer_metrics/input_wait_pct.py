"""Share of a fit's wall the step loop waited for input (``input_wait`` span
of ``Model.fit``, ``model.last_fit_telemetry``)."""


def read(ctx):
    frac = ctx.telemetry.get("input_wait_fraction")
    return None if frac is None else 100.0 * frac
