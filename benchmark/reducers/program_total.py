"""A sum of the program's own always-live set-up totals (PR 35): the wall
time of its set-up spans and jax's trace / compile / cache events, which the
program counts by phase (``stencil_tpu/telemetry/names.py PHASE_SERIES``,
``<epoch>.<total>.<phase>``).  Read in the process that ran the cell, after
the window -- as ``factories/common.py failure_counters`` reads the
``resilience.*`` counters -- so it needs no trace."""


def program_counters():
    """The program's counters, or nothing where it cannot say."""
    try:
        from stencil_tpu import telemetry

        return telemetry.snapshot()["counters"]
    except Exception:  # noqa: BLE001 -- a program without the facade reads as nothing
        return None


def reduce(ctx, series, phases, minus=()):
    """The sum over ``phases`` of every ``<series>.<phase>`` counter, less the
    same sum over ``minus``.  A registry that lacks one of them (a program
    from before PR 35) reads as nothing; 0 is a reading."""
    counters = ctx["program_counters"] if "program_counters" in ctx else program_counters()
    if counters is None:
        return None

    def total(bases):
        names = [f"{b}.{p}" for b in bases for p in phases]
        return None if any(n not in counters for n in names) else sum(counters[n] for n in names)

    plus, less = total(series), total(minus)
    return None if plus is None or less is None else plus - less
