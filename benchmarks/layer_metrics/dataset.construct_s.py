"""Seconds from `Dataset.construct()` to the binned matrix being on the
device (host clock, set-up). Layer: dataset. Moves: setup_s."""


def read(ctx):
    return ctx.get("construct_s")
