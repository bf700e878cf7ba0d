"""chip_smoke.py off the chip, and the pieces it stands on: the placeable
compile cache, the launcher's TPU process model, the no-re-exec dryrun."""

import os
import re
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PASS_LINE = '"ok": true'


def _smoke(*args, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env,
    )


def test_cpu_dryrun_rehearses_every_leg_and_never_passes():
    out = _smoke("--cpu-dryrun")
    sys.stderr.write(out.stdout[-3000:] + out.stderr[-3000:])
    assert out.returncode == 0
    assert out.stdout.rstrip().endswith("DRYRUN ok")
    assert PASS_LINE not in out.stdout
    for leg in ("kernel flash", "kernel gmm", "kernel embed_grad",
                "kernel rope", "kernel norm_rope", "kernel gdn_mix",
                "kernel gdn_gate", "kernel codec tiled",
                "leg A (gradient_allreduce): losses",
                "leg B (bytegrad): losses", "4-chip dp4: losses",
                "4-chip two-tier staged ZeRO: losses",
                "4-chip ring + int8 codec: losses", "4-chip eager:"):
        assert leg in out.stdout, leg


def test_without_a_chip_it_fails_and_prints_no_pass_line():
    out = _smoke()
    assert out.returncode != 0
    assert PASS_LINE not in out.stdout and "DRYRUN" not in out.stdout
    assert "chip_smoke needs a TPU" in out.stderr


# ---- the compile cache can be placed from outside -------------------------


def test_cache_env_var_is_the_only_configuration(monkeypatch):
    from bagua_tpu.compile_cache import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert configure_compile_cache() == "/some/dir"
    # jax read the variable itself at import; the code touched nothing
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_the_checkout(monkeypatch):
    # leaves the default in place: it is what every init_process_group call
    # in this suite sets anyway (and conftest keeps the cache disabled)
    from bagua_tpu.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_exactly_one_cache_dir_config_update_in_the_repo():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "__pycache__"]
        for name in files:
            if name.endswith(".py") and root != os.path.join(REPO, "tests"):
                text = open(os.path.join(root, name)).read()
                hits += [os.path.join(root, name)] * len(re.findall(
                    r"config\.update\(\s*[\"']jax_compilation_cache_dir",
                    text))
    assert hits == [os.path.join(REPO, "bagua_tpu", "compile_cache.py")]


def test_launcher_exports_the_cache_dir(monkeypatch):
    from bagua_tpu.distributed import run

    args = run.parse_args(["train.py"])
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert run.build_env(args, 0)["JAX_COMPILATION_CACHE_DIR"] == \
        os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert run.build_env(args, 0)["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


# ---- one process per TPU host ---------------------------------------------


def test_launcher_refuses_several_processes_on_a_tpu_host(monkeypatch,
                                                          capsys):
    from bagua_tpu.distributed import run

    monkeypatch.setattr(run, "_local_tpu_chips", lambda: 4)
    for platforms in (None, "tpu", "tpu,cpu"):
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(SystemExit) as exc:
            run.parse_args(["--nproc_per_node", "2", "train.py"])
        assert exc.value.code != 0
        assert "ONE process per host" in capsys.readouterr().err
    # the supported shape, and the CPU rehearsals, are unaffected
    assert run.parse_args(["train.py"]).nproc_per_node == 1
    assert run.parse_args(["--nproc_per_node", "2",
                           "--simulate_cpu_devices", "2", "train.py"])
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert run.parse_args(["--nproc_per_node", "2", "train.py"])


def test_dryrun_multichip_raises_on_too_few_devices():
    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="needs 99 devices"):
        graft.dryrun_multichip(99)
