"""One duration the harness took on the host's clock during set-up."""


def reduce(ctx, clock):
    return ctx["clocks"].get(clock)
