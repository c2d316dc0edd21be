"""Rows fed to histogram contractions per tree, over the rows of the
training set (`pass_log`'s rows_contracted; subtraction and compaction
lower it, full passes read about passes x rows). Layer: grower /
kernels. Moves: train_mrow_iters_per_s."""


def read(ctx):
    log = ctx.get("pass_log_window")
    if not log or not ctx.get("rows"):
        return None
    return sum(entry[2] for entry in log) / len(log) / ctx["rows"]
