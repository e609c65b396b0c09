"""chip_smoke.py's phases at tiny size on the virtual CPU mesh, so the script
the chip runs is not first executed on the chip.

A file of its own: pytest-xdist hands a file to one worker (``--dist
loadfile``), these cases take four minutes, and beside the compiles for a
described v5e (tests/test_tpu_compile.py, which sorts last of the long files)
they were the tail the whole run waited for.
"""

import time

import pytest

import chip_smoke

TINY = dict(
    config_name="cifar10_imp",  # ResNet18, 32x32
    batch=32,
    num_train=64,
    num_test=32,
    steps=2,
)


def test_chip_smoke_one_chip_phases_at_tiny_size(tmp_path):
    began = time.perf_counter()
    run = chip_smoke.phase_train(
        base_dir=tmp_path,
        platform="cpu",
        target_sparsity=0.3,
        num_devices=1,
        **TINY,
    )
    assert [r["level"] for r in run["levels"]] == [0, 1, 2]
    chip_smoke.phase_serve(
        expt_dir=run["expt_dir"],
        platform="cpu",
        request_sizes=(1, 3),
        final_level=2,
    )
    # What the smoke prints of a phase comes from the program's own record
    # of the modules that reached XLA (utils/tracing.py), on any thread.
    compile_s, modules, hits, misses = chip_smoke.compiled_since(began)
    assert "jit(train_step)" in modules and compile_s > 0 and (hits, misses) == (0, 0)


def test_chip_smoke_data_parallel_phase_at_tiny_size(tmp_path):
    chip_smoke.phase_data_parallel(
        devices=4,
        mask_tol=5e-2,
        base_dir=tmp_path,
        platform="cpu",
        target_sparsity=0.2,
        **TINY,
    )


def test_chip_smoke_ring_phase_at_tiny_size():
    chip_smoke.phase_ring(data=2, model=2, batch=4, seq=197, dim=384, heads=6)


def test_chip_smoke_refuses_to_start_without_a_tpu(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no phase ran, no result line
