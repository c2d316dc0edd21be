"""Padded pair slots the gradient program evaluates for one row of the
training set in one iteration: `rank.pair_slots` of the program's
`schedule_info` (the sum of Qb x D x D over the batches of every length
bucket, counted once in `LambdarankNDCG.init`) over the rows. Nothing to
read where the objective keeps no such counter. Layer: gradients. Moves:
train_mrow_iters_per_s."""


def read(ctx):
    rank = (ctx.get("schedule") or {}).get("rank")
    if not rank or not ctx.get("rows"):
        return None
    return rank["pair_slots"] / ctx["rows"]
