"""Peak of the buffers in use on the fullest device when the window closed
(memory_stats()["peak_bytes_in_use"]); the peak reserved for programs'
temporaries is printed on an earlier line."""


def read(obs):
    peak = obs["memory"].get("peak_bytes_in_use")
    return None if peak is None else peak / 2**30
