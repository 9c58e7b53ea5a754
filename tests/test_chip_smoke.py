"""CPU-tier guards around the chip smoke: the compile-cache placement rule
and the rehearsal of ``chip_smoke.py`` (control flow at tiny size; the
real run needs the chip and is the driver's)."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=300)


def test_compile_cache_dir_env_wins_else_fixed_checkout_path(tmp_path):
    probe = (
        "import jax\n"
        "from deeplearning4j_tpu.backend.compile_cache import "
        "enable_compile_cache\n"
        "{guard}"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        # a hit must not return another commit's scope names
        "assert jax.config.jax_compilation_cache_include_metadata_in_key\n")
    # placed from outside: JAX reads the variable, the code places nothing
    # (what it does set either way is the key's metadata flag, below)
    guard = ("_update = jax.config.update\n"
             "def _no(name, value):\n"
             "    assert 'cache_dir' not in name, name\n"
             "    _update(name, value)\n"
             "jax.config.update = _no\n")
    placed = str(tmp_path / "elsewhere")
    out = _run(["-c", probe.format(guard=guard)],
               JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [placed, placed]
    # not placed: one fixed path inside the checkout
    out = _run(["-c", probe.format(guard="")])
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [os.path.join(REPO, ".jax_cache")] * 2


def test_chip_smoke_rehearses_off_chip_and_demands_the_chip_otherwise(
        tmp_path):
    cache = str(tmp_path / "cache")
    in_checkout = os.path.join(REPO, ".jax_cache")
    listing = os.listdir(in_checkout) if os.path.isdir(in_checkout) else None
    out = _run(["chip_smoke.py", "--rehearsal"],
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_COMPILATION_CACHE_DIR=cache)
    assert out.returncode == 0, out.stderr[-3000:]
    assert '"ok"' not in out.stdout          # the success marker, never
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["train"]["steady_compiles"] == 0
    assert result["serve"]["steady_compiles"] == 0
    assert result["serve"]["requests"] >= 8 and result["serve"]["over_http"] >= 2
    assert result["dp"]["devices"] == 2      # the multi-device phase ran
    assert result["compile_cache"]["dir"] == cache
    # written where it was placed, and nowhere else
    assert result["compile_cache"]["entries_after"] == len(os.listdir(cache))
    assert len(os.listdir(cache)) >= 1
    assert listing == (os.listdir(in_checkout)
                       if os.path.isdir(in_checkout) else None)

    # without the argument it demands the chip: non-zero, no result line
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr.strip().splitlines()[-1]
