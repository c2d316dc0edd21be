"""What the readers of the dataset layer's phases share. The program
hangs the phases of a build on the dataset (`construct_record`, a
`telemetry.ConstructRecord`), but the mode frees the dataset before it
returns and hands a reader its result alone, so a reader asks for the
record of the dataset this process built last
(`telemetry.last_construct()`): a run of the benchmark builds one, in
set-up. It is taken only where it tells of the run's own table (`values`
is the run's rows times at most its features). Where the program has no
such record (the commits before PR 28), or built no dataset, there is
nothing to read."""


def field(ctx, name):
    """One field of the run's dataset's ConstructRecord, or None."""
    try:
        from lightgbm_tpu import telemetry
        record = telemetry.last_construct()
    except (ImportError, AttributeError):
        return None
    rows, features = ctx.get("rows"), ctx.get("features")
    if record is None or not rows or not features:
        return None
    if record.values % rows or record.values // rows > features:
        return None                 # another dataset was built since
    return getattr(record, name, None)
