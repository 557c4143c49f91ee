"""Where the persistent compile cache lives is decided from outside
(utils/compilecache.py): JAX_COMPILATION_CACHE_DIR when set — then no code
sets a directory — else one fixed path inside the checkout.  Plus a CPU
rehearsal of chip_smoke.py's phase plumbing, which must never pass."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, os, sys
import jax
from shifu_tpu.utils import compilecache
before = jax.config.jax_compilation_cache_dir
first = compilecache.enable_persistent_cache()
second = compilecache.enable_persistent_cache(min_compile_time_secs=0.0)
print(json.dumps({"before": before, "first": first, "second": second,
                  "active": compilecache.active_dir(),
                  "after": jax.config.jax_compilation_cache_dir,
                  "pid": os.getpid()}))
"""


def _probe(env_dir=None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR",
                        "SHIFU_TPU_NO_COMPILE_CACHE")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_directory_is_used_and_not_set_in_code(tmp_path):
    want = str(tmp_path / "from_outside")
    got = _probe(want)
    assert got["first"] == got["second"] == got["active"] == want
    # JAX read the variable itself at import; the call changed nothing
    assert got["before"] == got["after"] == want
    assert os.path.isdir(want)


def test_default_is_one_fixed_path_inside_the_checkout():
    a, b = _probe(), _probe()
    assert a["pid"] != b["pid"]
    want = os.path.join(REPO, ".jax_cache")
    for got in (a, b):
        assert got["first"] == got["second"] == got["active"] == want
        assert got["after"] == want
    # the checkout's own location plus a constant: no pid, time or temp
    # component can appear in it
    assert str(a["pid"]) not in want and str(b["pid"]) not in want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_VERDICT = """
import json, os, sys
import jax, jax.numpy as jnp
from shifu_tpu import obs
from shifu_tpu.obs import introspect
from shifu_tpu.utils import compilecache
path = compilecache.enable_persistent_cache(min_compile_time_secs=0.0)
# another writer to the directory, after this process looked at it
with open(os.path.join(path, "someone-elses-entry"), "w") as f:
    f.write("x")
journal = obs.RunJournal(None)
obs.set_journal(journal)
fn = introspect.instrument_jit(lambda x: jnp.tanh(x @ x.T).sum(), "probe")
fn(jnp.ones((24, 24), jnp.float32))
print(json.dumps([r["cache"] for r in journal.records
                  if r["kind"] == "xla_compile"]))
"""


def _verdicts(cache_dir, disabled=False) -> list:
    env = {k: v for k, v in os.environ.items()
           if k != "SHIFU_TPU_NO_COMPILE_CACHE"}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    env["SHIFU_TPU_XLA_COST"] = "0"
    out = subprocess.run([sys.executable, "-c", _VERDICT], env=env,
                         capture_output=True, text=True, timeout=180,
                         check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_the_verdict_is_jaxs_and_another_writer_does_not_flip_it(tmp_path):
    """`xla_compile.cache` is `miss` where JAX compiled and wrote the
    program and `hit` where its cache served it, whatever else appears in
    the directory meanwhile (the verdict was once read off a listing of it,
    where a new file from anyone read as a miss)."""
    cache_dir = str(tmp_path / "shared")
    assert _verdicts(cache_dir) == ["miss"]
    os.remove(os.path.join(cache_dir, "someone-elses-entry"))
    assert _verdicts(cache_dir) == ["hit"]


def test_the_module_keeps_no_listing_of_the_directory():
    from shifu_tpu.utils import compilecache

    assert not hasattr(compilecache, "observe_compile")
    assert not hasattr(compilecache, "_seen_entries")
    assert not hasattr(compilecache, "_list_entries")


def test_chip_smoke_rehearsal_runs_phases_and_never_passes(tmp_path):
    """device -> train (both tiers) -> serve at tiny size on the CPU: the
    plumbing holds, every line says REHEARSAL, and no result line prints."""
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearsal",
         "--phases", "device,train,serve", "--work", str(tmp_path / "work")],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines and all(line.startswith("REHEARSAL ") for line in lines)
    assert not any(line.lstrip().startswith("{") and '"ok"' in line
                   for line in lines)
    for phase in ("device", "train_resident", "train_staged", "client"):
        assert any(f"phase {phase}: rc=0" in line for line in lines), phase
    assert any("serve: SIGINT -> exit 0" in line for line in lines)


def test_chip_smoke_without_a_chip_fails_before_training(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--work", str(tmp_path / "work")],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "train" not in r.stdout
    assert "JAX found 'cpu'" in r.stderr
