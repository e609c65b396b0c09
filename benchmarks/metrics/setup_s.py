"""Process start to the instant the window opens: imports, data, init,
compilation or cache reads, and the warm-up units. Host clock."""


def read(obs):
    return obs["setup_s"]
