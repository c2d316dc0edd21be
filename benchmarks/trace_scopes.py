"""What the readers of `trace_scopes` share. A traced run of a mode that
keeps the trace long enough (`modes/train_steady_rank.py`) hands its
readers `{device plane: {scope: self seconds}}`, the program's own scope
names by `lightgbm_tpu.telemetry.devtrace`'s rule; a plane's scopes sum
to its busy seconds. Where the mode hands none there is nothing to
read."""


def layer_seconds(ctx, layer):
    """(self seconds under scope `layer` and its sub-scopes, busy
    seconds), each the mean over device planes; or None."""
    planes = ctx.get("trace_scopes")
    if not planes:
        return None
    mine = sum(s for scopes in planes.values() for name, s in scopes.items()
               if name == layer or name.startswith(layer + "/"))
    busy = sum(s for scopes in planes.values() for s in scopes.values())
    return mine / len(planes), busy / len(planes)
