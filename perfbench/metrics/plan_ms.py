"""Planner: mean host time of one ``ESGScheduler.plan`` call (a span the
benchmark puts around each call)."""


def read(run):
    return sum(run.plan_s) / len(run.plan_s) * 1e3 if run.plan_s else None
