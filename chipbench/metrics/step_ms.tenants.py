"""Mean host time of a BankSessionServer.step() that served at least
one tenant, in ms (the benchmark's span around each call)."""


def read(ctx):
    steps = ctx.counters.get("step_s")
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)
