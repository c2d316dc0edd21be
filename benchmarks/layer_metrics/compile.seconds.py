"""Seconds jax spent compiling or loading compiled programs during
set-up, summed from `telemetry/observer.py`'s record of
`backend_compile_duration` events. Layer: compile. Moves: setup_s."""


def read(ctx):
    comp = ctx.get("compile_setup")
    if not comp or not comp.get("count"):
        return None
    return comp["seconds"]
