"""The trainer's host loop on the profiler's clock: ``--profile-dir``
writes a trace of ``--profile-steps`` with one ``train`` span per step and
the ``data`` / ``read_loss`` spans inside it, and the history's
``seconds`` is the mean blocked step time between loss reads, with each
compiling call timed on its own."""
import glob
import os
import statistics

import numpy as np
import pytest

from repro.launch import train

ARGS = ["--arch", "mamba2-130m", "--smoke", "--batch", "2", "--seq", "32",
        "--data-shards", "1"]


def host_spans(profile_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events]
    return spans


@pytest.mark.parametrize("method", ["none", "lgc_ps"])
def test_profile_window_holds_the_host_spans(tmp_path, method):
    prof = str(tmp_path / "prof")
    hist = train.main(ARGS + ["--compression", method, "--steps", "5",
                              "--warmup-steps", "0", "--ae-train-steps",
                              "0", "--log-every", "1", "--profile-dir",
                              prof, "--profile-steps", "2:4"])
    assert [h["step"] for h in hist] == list(range(5))
    spans = host_spans(prof)
    steps = sorted((s, e) for name, s, e in spans if name == "train")
    assert len(steps) == 2                 # steps 2 and 3, nothing else
    for name in ("data", "read_loss"):
        inside = [(s, e) for n, s, e in spans if n == name
                  and any(a <= s and e <= b for a, b in steps)]
        assert len(inside) == 2, name


def test_seconds_keep_each_compile_apart():
    """Phase 3 starts at step 3: its first call compiles and is timed
    alone, so the steady steps around it read far less."""
    hist = train.main(ARGS + ["--compression", "lgc_rar", "--steps", "7",
                              "--warmup-steps", "1", "--ae-train-steps",
                              "2", "--log-every", "1"])
    sec = {h["step"]: h["seconds"] for h in hist}
    first = [0, 1, 3]                      # each phase's first call
    steady = [2, 4, 5, 6]
    assert min(sec[s] for s in first) > \
        3 * statistics.median(sec[s] for s in steady)
    assert all(sec[s] > 0 for s in sec)


def test_seconds_average_the_steps_between_reads():
    """With a loss read every third step, a logged step's seconds is the
    mean over the three steps since the last read, not the time of its
    own dispatch."""
    hist = train.main(ARGS + ["--compression", "none", "--steps", "7",
                              "--log-every", "3"])
    assert [h["step"] for h in hist] == [0, 3, 6]
    sec = [h["seconds"] for h in hist]
    assert sec[0] > 3 * statistics.median(sec[1:])   # it compiles
    assert np.isfinite(sec).all() and min(sec) > 0


def test_step_clock(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(train.time, "time", lambda: now[0])
    clock = train.StepClock(0)
    now[0] = 103.0
    assert clock.read(2) == pytest.approx(1.0)      # steps 0-2, 3 s
    now[0] = 104.0
    clock.restart(5)                                # steps 3-4 unread
    now[0] = 110.0
    assert clock.read(5) == pytest.approx(6.0)      # step 5 alone
    now[0] = 112.0
    assert clock.read(9) == pytest.approx(0.5)      # steps 6-9


def test_compile_cache_keys_include_the_scopes(tmp_path):
    """Two programs that differ only in a named scope get two cache
    entries, each compiled with its own scopes in its text."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        texts = []
        for name in ("scope_a", "scope_b"):
            def f(x):
                with jax.named_scope(name):
                    return jnp.sin(x) * 2
            texts.append(jax.jit(f).lower(jnp.ones(8)).compile().as_text())
        assert "scope_a" in texts[0] and "scope_b" in texts[1]
        assert len(list(tmp_path.iterdir())) >= 2
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
        cc.reset_cache()
