"""Device time of one training step: the durations of the step program's
executions in the trace (the name is the cell's ``step_program``, one
execution runs ``steps_per_epoch`` steps), divided by the steps in them."""


def read(obs):
    if obs["trace"] is None or "step_program" not in obs:
        return None
    runs = [
        d
        for name, durations in obs["trace"]["modules"].items()
        if name.startswith(obs["step_program"])
        for d in durations
    ]
    if not runs:
        return None
    return 1e3 * sum(runs) / (len(runs) * obs["steps_per_epoch"])
