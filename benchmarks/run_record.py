"""What the readers of the set-up records share. The program keeps what
`GBDT.init` took (`init_record`, a `telemetry.InitRecord`) and one
`telemetry.TreeRecord` a tree (`pass_log`) on the booster, but the mode
frees the booster before it returns and hands a reader its result alone,
with the window's trees only (`pass_log_window`). So a reader asks for
the records of the booster this process initialised last
(`telemetry.last_run()`: the dataset's `ConstructRecord`, the
`InitRecord`, and that booster's whole `pass_log`): a run of the
benchmark initialises one, in set-up. They are taken only where they tell
of the run's own booster (`init.rows` is the run's rows). The warm-up
trees are the ones before the window's. Where the program keeps no such
records (the commits before PR 36), there is nothing to read."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tree_record  # noqa: E402


def last_run(ctx):
    """(construct, init, trees) of the run's booster, or None."""
    try:
        from lightgbm_tpu import telemetry
        run = telemetry.last_run()
    except (ImportError, AttributeError):
        return None
    if run is None or not ctx.get("rows"):
        return None
    if int(run[1].rows) != int(ctx["rows"]):
        return None                 # another booster was initialised since
    return run


def init_field(ctx, name):
    """One field of the run's InitRecord, or None."""
    run = last_run(ctx)
    return None if run is None else getattr(run[1], name, None)


def warmup_trees(ctx):
    """The TreeRecords of the trees grown before the window opened (the
    ones that hold the tracing, the cache loads and each program's first
    run), or None where there is none or the window's are not known."""
    run = last_run(ctx)
    window = ctx.get("pass_log_window")
    if run is None or not window:
        return None
    trees = run[2]
    return list(trees[:len(trees) - len(window)]) or None


def setup_sum(ctx, name):
    """`name` of the InitRecord plus the same field of every warm-up
    tree: a compile-path number over all of the program's set-up."""
    first = init_field(ctx, name)
    trees = warmup_trees(ctx)
    if first is None or trees is None:
        return None
    return first + sum(getattr(t, name) for t in trees)


def warmup_overhead_s(ctx):
    """What the warm-up trees cost beyond a tree of the window: the sum
    over them of `dispatch_s + fetch_wait_s + build_tree_s`, less their
    number times the window's mean of the same sum."""
    trees = warmup_trees(ctx)
    parts = [tree_record.column(ctx, f)
             for f in ("dispatch_s", "fetch_wait_s", "build_tree_s")]
    if trees is None or any(p is None for p in parts):
        return None
    a_tree = sum(tree_record.mean(p) for p in parts)
    took = sum(t.dispatch_s + t.fetch_wait_s + t.build_tree_s for t in trees)
    return took - len(trees) * a_tree
