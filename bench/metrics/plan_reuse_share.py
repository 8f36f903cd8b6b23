"""Percent of the tables publish merged in the window that committed the
merge plan the CI checks' preview had made, rather than planning again
(``publish.plans_reused`` against ``publish.plans_replanned``); None where
the program has no such counters or no publish ran."""


def read(ctx):
    if "publish.plans_reused" not in ctx.counters:
        return None
    reused = ctx.counters["publish.plans_reused"]
    tables = reused + ctx.counters.get("publish.plans_replanned", 0)
    return 100.0 * reused / tables if tables else None
