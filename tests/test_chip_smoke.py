"""CPU rehearsals of ``chip_smoke.py`` at a tiny size.

The script runs only on a TPU; these tests patch its platform check and
shrink its sizes from outside, in a child process with forced host
devices, and check what it prints: records identical to the oracle, the
contract line last, and compile-cache hits on a second run.
"""
import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REHEARSAL = """
import os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
sys.path.insert(0, {repo!r})
import jax
import chip_smoke as cs
cs.require_tpu = lambda: jax.devices()[0]
cs.N_TOKENS, cs.VOCAB = 2 ** 15, 2 ** 10
cs.TASK_SIZE, cs.PUSH_CAP, cs.SEGMENT = 256, 64, 4
cs.main({argv!r})
"""


def _rehearse(devices8, tmp_path, n_devices, argv=()):
    code = REHEARSAL.format(cache=str(tmp_path / "cache"), repo=REPO,
                            argv=list(argv))
    lines = devices8(code, n_devices=n_devices).strip().splitlines()
    return lines, json.loads(lines[-1])


def _field(lines, label, pattern):
    for line in lines:
        if line.startswith(label + ":"):
            m = re.search(pattern, line)
            if m:
                return int(m.group(1))
    raise AssertionError(f"no {pattern!r} on a {label!r} line: {lines}")


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_one_device_rehearsal_and_cache_hits(devices8, tmp_path):
    lines, last = _rehearse(devices8, tmp_path, 1)
    assert last == {"ok": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert "records identical across 2 runs and the oracle" in lines
    assert any((tmp_path / "cache").iterdir())
    # a second process finds the first one's programs in the cache
    lines, _ = _rehearse(devices8, tmp_path, 1)
    assert _field(lines, "2s", r"cache hits (\d+)") >= 1
    assert _field(lines, "2s", r"misses (\d+)") == 0


def test_chip_smoke_four_device_rehearsal(devices8, tmp_path):
    lines, last = _rehearse(devices8, tmp_path, 4, ["--chips", "4"])
    assert last["ok"] is True and last["device"]["count"] == 4
    assert "records identical across 3 runs and the oracle" in lines
    # the unbalanced grid makes the stealing run move work between ranks
    assert _field(lines, "1s +steal", r"steals (\d+)") > 0
    assert _field(lines, "1s", r"steals (\d+)") == 0


def test_compile_cache_defaults_to_the_checkout(devices8):
    out = devices8("""
        import os
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        from repro import compile_cache
        print(compile_cache.enable())
    """, n_devices=1)
    assert out.strip().splitlines()[-1] == os.path.join(REPO, ".jax_cache")


def test_compile_cache_honors_the_environment(devices8, tmp_path):
    out = devices8(f"""
        import os
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        import jax, jax.numpy as jnp
        from repro import compile_cache
        print(compile_cache.enable())
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
    """, n_devices=1)
    assert out.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir())
