"""Start-up and device handling: the checks that keep a run from hiding
which device it is on. No model is compiled here; the file sorts first so
these run before the compile-heavy suites.

On the chip the same rules are proven by ``python chip_smoke.py``; here
they are pinned on the CPU backend: the smoke refuses anything but a TPU,
the compile cache is placed from outside, ``attention=flash`` is refused
where it could only ever run the oracle, an unknown TPU has no silent
peak, ``--isolation process`` is refused on a TPU, and a snapshot is split
into objects the machine's file-size limit lets through.
"""

import errno
import os
import resource
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import chip_smoke
from mingpt_distributed_tpu.config import ConfigError, GPTConfig, OptimizerConfig
from mingpt_distributed_tpu.parallel import distributed
from mingpt_distributed_tpu.telemetry import peaks
from mingpt_distributed_tpu.training import checkpoint, durability
from mingpt_distributed_tpu.training.optimizer import make_optimizer
from mingpt_distributed_tpu.training.trainer import make_train_step
from mingpt_distributed_tpu.utils import startup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Size(
    model={"n_layer": 1, "n_head": 2, "n_embd": 32},
    vocab=300, block=32, corpus_chars=2000, batch_per_device=2, steps=2,
    prompt_lens=(5, 20), slots=2, new_tokens=4,
    kernel_shapes=((1, 32, 2, 16),),
)


def _clean_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", *chip_smoke.FLASH_ENV)}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------


def test_chip_smoke_fails_without_a_tpu_and_names_what_it_found():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=_clean_env(), cwd=REPO,
    )
    assert proc.returncode != 0
    assert "platform=cpu" in proc.stdout, proc.stdout
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr, proc.stderr
    # no result line: nothing on stdout parses as the {"ok": ...} object
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("var", chip_smoke.FLASH_ENV)
def test_chip_smoke_refuses_kernel_overrides(monkeypatch, var):
    """The smoke proves the kernels the program picks by itself."""
    monkeypatch.setenv(var, "1")
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(chip_smoke.subprocess, "run", lambda *a, **k: pytest.fail(
        "a leg was started with a kernel override in the environment"))
    with pytest.raises(SystemExit, match=var):
        chip_smoke.main()


def test_chip_smoke_refuses_a_snapshot_it_did_not_write(tmp_path):
    """The trainer resumes from whatever it finds, and with max_steps
    already reached it trains nothing and exits 0."""
    out = str(tmp_path)
    chip_smoke.write_inputs(out, TINY)
    with open(chip_smoke.paths(out)["snapshot"], "wb") as f:
        f.write(b"stale")
    with pytest.raises(SystemExit, match="snapshot already exists"):
        chip_smoke.leg_train(out, TINY)


def test_chip_smoke_corpus_carries_the_whole_vocabulary(tmp_path):
    out = str(tmp_path)
    chip_smoke.write_inputs(out, TINY)
    p = chip_smoke.paths(out)
    with open(p["corpus"], encoding="utf-8") as f:
        text = f.read()
    assert len(text) == TINY.corpus_chars
    assert len(set(text)) == TINY.vocab  # CharDataset's vocab_size
    with open(p["prompts"], encoding="utf-8") as f:
        prompts = f.read().splitlines()
    half = len(TINY.prompt_lens)
    assert [len(x) for x in prompts[:half]] == list(TINY.prompt_lens)
    assert prompts[:half] == prompts[half:]  # each prompt twice
    # GPT-2's vocabulary: distinct, one line each, and UTF-8 round-trips
    # (no surrogates)
    full = chip_smoke.alphabet(chip_smoke.FULL.vocab)
    assert len(set(full)) == 50257
    joined = "".join(full)
    assert joined.encode("utf-8").decode("utf-8") == joined
    assert len(joined.splitlines()) == 1


# ---------------------------------------------------------------------------
# compile cache, device line, single-host start-up
# ---------------------------------------------------------------------------


def test_exported_cache_dir_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: pytest.fail(
        "a cache directory was set in code over the exported one"))
    assert startup.enable_compile_cache() == str(tmp_path)


def test_default_cache_dir_is_the_same_in_checkout_path_from_two_processes(
        tmp_path):
    code = ("import jax; from mingpt_distributed_tpu.utils import startup; "
            "print(startup.enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = _clean_env(PYTHONPATH=REPO)
    # different working directories: the path comes from the package's
    # location, not from where the process was started
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env, cwd=cwd,
                              stdout=subprocess.PIPE, text=True)
             for cwd in (REPO, str(tmp_path))]
    outs = [p.communicate(timeout=120)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]


def test_device_line_names_platform_kind_and_count():
    line = startup.device_line()
    assert "platform=cpu" in line and "device_kind='cpu'" in line
    assert f"count={len(jax.devices())}" in line


def test_one_host_tpu_environment_touches_no_network(monkeypatch):
    """A one-host TPU VM exports the pod-worker variables; only an explicit
    coordinator address makes the program join a multi-host job."""
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.delenv("COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setattr(jax.distributed, "initialize", lambda *a, **k: pytest.fail(
        "single-host start-up went looking for a cluster"))
    distributed.initialize()
    assert distributed._initialized is False


# ---------------------------------------------------------------------------
# attention=flash means the kernel, or an error
# ---------------------------------------------------------------------------


def _flash_cfg(**kw):
    base = dict(n_layer=1, n_head=2, n_embd=32, vocab_size=64, block_size=128,
                embd_pdrop=0.1, resid_pdrop=0.1, attn_pdrop=0.0,
                attention="flash")
    base.update(kw)
    return GPTConfig.make(**base)


def test_flash_with_attention_dropout_is_refused_by_name():
    optimizer = make_optimizer(OptimizerConfig(), grad_norm_clip=1.0)
    with pytest.raises(ConfigError, match=r"attn_pdrop=0\.1"):
        make_train_step(_flash_cfg(attn_pdrop=0.1), optimizer)
    # embedding and residual dropout do not gate the kernel
    make_train_step(_flash_cfg(), optimizer)
    # the oracle takes any dropout
    make_train_step(_flash_cfg(attn_pdrop=0.1, attention="einsum"), optimizer)


def test_flash_with_untileable_sequence_is_refused_at_trace_time():
    optimizer = make_optimizer(OptimizerConfig(), grad_norm_clip=1.0)
    step = make_train_step(_flash_cfg(), optimizer)
    tokens = jax.ShapeDtypeStruct((2, 100), jax.numpy.int32)  # 100 % 8 != 0
    with pytest.raises(ConfigError, match="T=100"):
        # raised before the state is touched: no params needed to see it
        jax.eval_shape(step, {"step": 0}, (tokens, tokens), jax.random.key(0))


# ---------------------------------------------------------------------------
# peaks, process isolation
# ---------------------------------------------------------------------------


def _fake_devices(platform, kind):
    return lambda: [types.SimpleNamespace(platform=platform, device_kind=kind)]


def test_peak_lookup_raises_for_unknown_tpu_and_is_none_on_cpu(monkeypatch):
    assert peaks.peak_flops_per_chip() is None  # this backend: CPU
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v5 lite"))
    assert peaks.peak_flops_per_chip() == 197e12
    monkeypatch.setattr(jax, "devices", _fake_devices("tpu", "TPU v99"))
    for lookup in (peaks.peak_flops_per_chip, peaks.peak_hbm_bytes_per_chip):
        with pytest.raises(LookupError, match="TPU v99"):
            lookup()


def test_isolation_process_is_refused_on_a_tpu():
    import serve

    with pytest.raises(SystemExit, match="one process"):
        serve._check_isolation("process", "tpu")
    serve._check_isolation("process", "cpu")
    serve._check_isolation("thread", "tpu")


# ---------------------------------------------------------------------------
# snapshot objects against the machine's file-size limit
# ---------------------------------------------------------------------------


def test_snapshot_is_split_to_fit_the_file_size_limit(tmp_path):
    """GPT-2 124M's params and Adam moments are 1.96 GB; written as one file
    on a machine that limits file size, the save died with EFBIG after the
    last step. The shard count follows the limit the process can observe."""
    assert durability.max_object_bytes() <= durability.MAX_OBJECT_BYTES
    params = {"w": np.arange(300_000, dtype=np.float32)}  # 1.2 MB
    path = str(tmp_path / "snap.msgpack")
    limit = 256 * 1024
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
    try:
        assert durability.max_object_bytes() == limit // 2
        with pytest.raises(OSError) as too_large:  # the limit is real here
            durability.write_bytes(path, params["w"].tobytes(),
                                   durability.NO_WAIT)
        checkpoint.save_snapshot(
            path, checkpoint.Snapshot(params=params, opt_state={}, step=3))
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))
    # retrying cannot make the same bytes fit
    assert too_large.value.errno == errno.EFBIG
    assert durability.classify_io_error(too_large.value) == durability.PERMANENT
    entry = durability.load_manifest(path).latest
    assert len(entry.shards) == 10
    assert max(ref.size for ref in entry.shards) <= limit
    # any shard count restores: the chunking is layout-independent
    snap = checkpoint.load_snapshot(path, params)
    assert snap.step == 3
    np.testing.assert_array_equal(snap.params["w"], params["w"])
