"""chip_smoke.py on the CPU: it refuses to run without a TPU or outside a
checkout, and its phases pass at reduced widths (the kernel-on-the-path
check, which needs Mosaic, must fail in interpret mode)."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], capture_output=True,
                          text=True, timeout=300, cwd=cwd, env=env)


def test_refuses_without_tpu(tmp_path):
    res = _run_smoke(ROOT / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "found no TPU" in res.stderr


def test_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    res = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no src/repro" in res.stderr


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


def test_phases_at_reduced_widths(smoke):
    flags = dict(zip(smoke.SERVE_ARGV[::2], smoke.SERVE_ARGV[1::2]))
    assert flags["--widths"] == "published"
    flags.update({"--widths": "reduced", "--requests": "6",
                  "--ragged-min": "8", "--ragged-max": "24",
                  "--block-size": "4", "--prefill-chunk": "8",
                  "--max-new": "4"})
    argv = [x for kv in flags.items() for x in kv]
    engine, res = smoke.serve_phase(argv)
    assert engine.small.cfg.name.endswith("-reduced")
    probe = smoke.prefilled(engine.small, engine.block_size, 1)
    smoke.logits_phase(engine.small, probe)
    with pytest.raises(RuntimeError, match="no Pallas kernel"):
        smoke.kernel_phase(engine, probe, 4)
