"""The offline CLIs exit nonzero when work is lost, and the compile-cache
and XLA-flag helpers they call from ``main()``.

Each CLI runs in-process at smoke scale through its ``main()``; the exit
status is the one the interpreter would give: a normal return is 0, a
``SystemExit`` carries its code (a message means 1), and any other
escaping exception is 1.
"""
import math
import os
import sys

import jax
import pytest

from repro.launch import compile_cache, mesh, serve, train


def _exit_status(main, argv, monkeypatch) -> int:
    monkeypatch.setattr(sys, "argv", argv)
    try:
        main()
    except SystemExit as e:
        return 0 if e.code is None else e.code if isinstance(e.code, int) \
            else 1
    except Exception:
        return 1
    return 0


@pytest.fixture
def cli_env(monkeypatch, tmp_path):
    """Name a cache in the environment so the CLIs configure none (tests
    must not write into the checkout's cache), and restore the XLA flags
    ``main()`` extends."""
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / "jax_cache"))
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))


SERVE = ["serve", "--arch", "smollm-135m", "--smoke", "--requests", "3",
         "--slots", "2", "--prompt-len", "8", "--gen-len", "4"]


@pytest.mark.parametrize("fault", [None, "admission"])
def test_serve_cli_exit_status(monkeypatch, cli_env, fault):
    if fault == "admission":
        def broken_admit(self, rs, clock, resv=None):
            raise RuntimeError("injected admission failure")
        monkeypatch.setattr(serve.Engine, "_admit", broken_admit)
    status = _exit_status(serve.main, SERVE, monkeypatch)
    assert status == (0 if fault is None else 1)


TRAIN = ["train", "--arch", "smollm-135m", "--smoke", "--steps", "2",
         "--batch", "2", "--seq", "16", "--save-every", "100"]


@pytest.mark.parametrize("fault", [None, "raise", "inf_loss"])
def test_train_cli_exit_status(monkeypatch, cli_env, tmp_path, fault):
    build = train.build_train_step

    def faulty_build(*a, **kw):
        step = build(*a, **kw)

        def faulty_step(state, batch):
            new_state, metrics = step(state, batch)
            if fault == "raise":
                raise RuntimeError("injected step failure")
            return new_state, dict(metrics, loss=metrics["loss"] * math.inf)
        return faulty_step

    if fault is not None:
        monkeypatch.setattr(train, "build_train_step", faulty_build)
    argv = TRAIN + ["--ckpt-dir", str(tmp_path / "ckpt")]
    status = _exit_status(train.main, argv, monkeypatch)
    assert status == (0 if fault is None else 1)


def test_compile_cache_env_set_configures_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_unset_uses_checkout_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.CHECKOUT_CACHE_DIR
        assert got.name == ".jax_cache"
        assert (got.parent / "src" / "repro" / "launch").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(got)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_require_exact_rounding_adds_the_flag_once(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    mesh.require_exact_rounding()
    mesh.require_exact_rounding()
    assert os.environ["XLA_FLAGS"].split() == [
        "--xla_dump_to=x", mesh.EXACT_ROUNDING_FLAG]


def test_serve_cli_jax_profile_writes_the_engine_spans(monkeypatch, cli_env,
                                                       tmp_path):
    from jax.profiler import ProfileData
    argv = SERVE + ["--jax-profile", str(tmp_path / "profile")]
    assert _exit_status(serve.main, argv, monkeypatch) == 0
    path = next((tmp_path / "profile").rglob("*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert {"repro.engine.step", "repro.engine.decode.wait"} <= names
