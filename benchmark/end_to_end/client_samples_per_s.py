"""Cohort samples trained (clients x real samples x local epochs of every completed
round) over the whole window, host time between rounds included."""


def read(ctx):
    return ctx["client_samples"] / ctx["window_s"]
