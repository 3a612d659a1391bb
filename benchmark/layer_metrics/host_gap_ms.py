"""Host time a round costs outside the round itself: the benchmark's clock around each
generator step, less the duration the program reports for the round it ran inside it
(publish, ledger, retune check, generator bookkeeping), averaged over the window."""


def read(ctx):
    rounds = ctx["rounds"]
    if not rounds:
        return None
    return 1000.0 * sum(step_s - m.duration_s for step_s, m in rounds) / len(rounds)
