"""``chip_smoke.py`` never falls back to the CPU: without a TPU it exits
non-zero, says why, and prints no result line."""
import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_tpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    assert smoke.main([]) == 1
    assert smoke.main(["--four-chips"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU found" in out.err
