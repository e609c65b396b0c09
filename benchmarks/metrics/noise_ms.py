"""Device time of one epoch's noising: the durations of the token loader's
noising program in the trace (the name is the job's ``noise_program``), per
execution; one execution draws a level a block and a mask a token for a whole
epoch's sequences. Nothing to read where the job names no such program or the
trace holds none."""


def read(obs):
    if obs["trace"] is None or "noise_program" not in obs:
        return None
    runs = [
        d
        for name, durations in obs["trace"]["modules"].items()
        if name.startswith(obs["noise_program"])
        for d in durations
    ]
    return 1e3 * sum(runs) / len(runs) if runs else None
