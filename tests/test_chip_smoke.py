"""chip_smoke.py at a tiny scale on the CPU: its phases and checks keep
working between chip runs, and it refuses to report without a TPU."""

import importlib.util
import pathlib
import sys


def _load_smoke():
    path = pathlib.Path(__file__).resolve().parent.parent / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations through it
    spec.loader.exec_module(mod)
    return mod


def test_every_check_passes_at_tiny_scale(capsys):
    smoke = _load_smoke()
    tiny = smoke.Sizes(
        batch=64, minibatches=2, runs=(1, 1), nodes=2, cache_rows=8_000,
        file_rows=1_024, bag_rows=5_003, adagrad_rows=1_003, extract_examples=64,
        topk_shape=(8, 1_024, 8), serve_keys=64, retrieve_queries=4, retrieve_k=8,
    )
    assert smoke.run(tiny, seed=0) == []
    out = capsys.readouterr().out
    assert out.count("[PASS]") == 12 and "[FAIL]" not in out


def test_exits_nonzero_without_a_tpu(capsys):
    smoke = _load_smoke()
    assert smoke.main([]) == 2
    assert '"ok"' not in capsys.readouterr().out
