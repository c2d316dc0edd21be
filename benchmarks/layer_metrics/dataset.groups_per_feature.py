"""Stored columns over used features: `efb.groups / efb.features` of the
program's `schedule_info` (`telemetry.EfbCounters`, counted once in
`ingest/build.build_inner`). 1 where nothing is bundled; every pass of
the grower reads this share of the table's width. Nothing to read where
the program keeps no such counters or bundled nothing. Layer: dataset.
Moves: train_mrow_iters_per_s."""


def read(ctx):
    efb = (ctx.get("schedule") or {}).get("efb")
    if not efb or not efb.get("features"):
        return None
    return efb["groups"] / efb["features"]
