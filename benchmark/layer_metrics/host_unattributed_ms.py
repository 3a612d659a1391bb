"""What the program's tiling of a round misses, on the benchmark's clock: each generator
step as the loop timed it, less the sum of the five segments the program reports for the
round inside it (``RoundMetrics.segments``), averaged over the window's rounds.  Left out
where a round carries no segments."""


def read(ctx):
    rounds = ctx["rounds"]
    segments = [getattr(m, "segments", None) for _, m in rounds]
    if not segments or not all(segments):
        return None
    missed = sum(step_s - sum(s.values()) for (step_s, _), s in zip(rounds, segments))
    return 1000.0 * missed / len(rounds)
