"""Mean histogram passes per tree over the window's trees, from
`GBDT.pass_log` (fetched with each tree). Layer: grower. Moves:
train_mrow_iters_per_s."""


def read(ctx):
    log = ctx.get("pass_log_window")
    if not log:
        return None
    return sum(entry[0] for entry in log) / len(log)
