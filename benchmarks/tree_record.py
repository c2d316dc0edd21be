"""What the readers of `GBDT.pass_log` share. The harness hands each
per-tree record over as a plain list (`pass_log_window`), so a field is
found by its position in the program's `telemetry.TreeRecord`. Where the
program has no such record, or a record is too short to hold the field
(the commits before PR 26), there is nothing to read."""


def column(ctx, field):
    """The window's values of one record field, or None."""
    log = ctx.get("pass_log_window")
    if not log:
        return None
    try:
        from lightgbm_tpu.telemetry import TreeRecord
        at = TreeRecord._fields.index(field)
    except (ImportError, ValueError):
        return None
    if any(len(entry) <= at for entry in log):
        return None
    return [entry[at] for entry in log]


def mean(values):
    return sum(values) / len(values)
