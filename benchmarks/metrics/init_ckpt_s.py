"""The checkpoints a fresh run writes before it trains: ``ckpt/fetch``,
``ckpt/write`` and ``ckpt/barrier`` under this run's first ``level/setup``
(``model_init`` and ``optimizer_init``, the rewind targets). A resumed run's
first set-up writes none and reads 0."""

from benchmarks import program_spans

_PARTS = ("ckpt/fetch", "ckpt/write", "ckpt/barrier")


def read(obs):
    t1 = obs["window"][0]
    spans = program_spans.recorded(None, t1 - obs["setup_s"], t1)
    setup = next((s for s in spans if s.name == "level/setup"), None)
    if setup is None:
        return None
    by_id = {s.id: s for s in spans}

    def under_setup(s):
        while s is not None and s.id != setup.id:
            s = by_id.get(s.parent)
        return s is not None

    return sum(s.seconds for s in spans if s.name in _PARTS and under_setup(s))
