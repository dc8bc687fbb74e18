"""Build records (`obs.builds`): each program's trace, lowering and compile
(or cache read) and its first run, tagged with the program, and Python's
collections on the same clock. Tiny jits only."""

import gc
import itertools

import jax
import jax.numpy as jnp
import pytest

from alphafold2_tpu import Alphafold2, obs
from alphafold2_tpu.obs import builds
from alphafold2_tpu.serve import FoldExecutor
from benchmark.layer_metrics import setup_stage_s

_names = itertools.count()


def _booked(since: int, program: str):
    return [r for r in builds.records()[since:] if r["program"] == program]


def _tiny_program():
    """A fresh closure over two inner jits, and a fresh name for it."""
    @jax.jit
    def inner(x):
        return jnp.sin(x) @ x

    def body(x):
        return inner(x) + jnp.cos(x).sum()
    return body, f"tiny{next(_names)}"


def test_a_tagged_build_is_one_record_a_stage_inner_traces_folded_in():
    body, tag = _tiny_program()
    x = jnp.ones((4, 4))
    since = len(builds.records())
    with builds.program(tag):
        jax.jit(body).lower(x).compile()
    booked = _booked(since, tag)
    assert [r["stage"] for r in booked] == ["trace", "lower", "compile"]
    assert all(r["tagged"] and r["build"] == 1 for r in booked)
    assert booked[0]["fun_name"] == "body"
    assert booked[1]["fun_name"] == booked[2]["fun_name"] == "jit(body)"
    # `inner`, `sin`, `cos`, ... traced inside `body`: no record of their own
    assert not [r for r in builds.records()[since:]
                if r["fun_name"] in ("inner", "sin", "cos")]
    assert all(0 <= r["end"] - r["start"] for r in booked)
    assert booked[0]["end"] <= booked[1]["start"] <= booked[2]["start"]


def test_a_wrapper_jitted_around_a_marked_body_is_tagged():
    body, tag = _tiny_program()

    def step(x):
        builds.mark(tag)
        return body(x)

    def wrapper(x):
        return step(x) * 2.0

    since = len(builds.records())
    jax.jit(wrapper)(jnp.ones((4, 4)))
    booked = _booked(since, tag)
    assert [(r["stage"], r["fun_name"]) for r in booked] == [
        ("trace", "wrapper"), ("lower", "jit(wrapper)"),
        ("compile", "jit(wrapper)")]
    step(jnp.ones((4, 4)))            # run eagerly: no build to mark
    assert _booked(since, tag) == booked


def test_a_second_executor_builds_the_key_again_and_the_first_build_counts():
    model = Alphafold2(dim=16, depth=1, heads=2, dim_head=8,
                       predict_coords=True, structure_module_depth=1)
    n = 8
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, n), jnp.int32),
                        mask=jnp.ones((1, n), bool))
    since = len(builds.records())
    for _ in range(2):
        FoldExecutor(model, params).warmup([(n, 1, 0, 0)])
    booked = _booked(since, "fold/8x1/m0/r0")
    stages = ["trace", "lower", "compile", "first_run"]
    assert [r["stage"] for r in booked] == stages * 2
    first, second = booked[0]["build"], booked[4]["build"]
    assert second == first + 1
    assert {r["build"] for r in booked[:4]} == {first}
    builds_by_tag = setup_stage_s.first_builds(
        [dict(r, build=r["build"] - first + 1) for r in booked])
    summed = builds_by_tag["fold/8x1/m0/r0"]
    for stage, record in zip(stages, booked[:4]):
        assert summed[stage] == pytest.approx(record["end"] - record["start"])
    assert summed["end"] == booked[3]["end"]


def test_the_first_build_rule_leaves_later_and_untagged_builds_out():
    def rec(program, stage, build, start, end, tagged=True, cache="none"):
        return {"program": program, "fun_name": "f", "stage": stage,
                "start": start, "end": end, "cache": cache,
                "tagged": tagged, "build": build}
    records = [rec("draw", "compile", 1, 0.0, 5.0, tagged=False),
               rec("fold/8x1/m0/r0", "trace", 1, 1.0, 3.0),
               rec("fold/8x1/m0/r0", "compile", 1, 3.0, 4.0, cache="hit"),
               rec("fold/8x1/m0/r0", "first_run", 1, 4.0, 4.5),
               rec("fold/8x1/m0/r0", "trace", 2, 9.0, 19.0)]
    assert setup_stage_s.first_builds(records) == {"fold/8x1/m0/r0": {
        "trace": 2.0, "compile": 1.0, "first_run": 0.5, "cache": "hit",
        "end": 4.5}}


def test_a_rebuild_with_a_fresh_closure_reads_the_persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        tag, x = f"cached{next(_names)}", jnp.ones((3, 3))
        since = len(builds.records())
        for _ in range(2):
            body, _ = _tiny_program()
            with builds.program(tag):
                jax.jit(body).lower(x).compile()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    compiles = [r for r in _booked(since, tag) if r["stage"] == "compile"]
    assert [r["cache"] for r in compiles] == ["miss", "hit"]
    assert obs.get_registry().counter(
        "af2_builds_total", "", ("program", "cache")).value(
        program=tag, cache="hit") == 1


def test_a_forced_collection_is_booked_with_its_generation():
    pauses = obs.get_registry().histogram(
        "af2_gc_pause_seconds", "", ("generation",))
    before = pauses.count(generation=2)
    since = max((c["start"] for c in builds.collections()), default=0.0)
    gc.collect()
    forced = [c for c in builds.collections(since) if c["start"] > since
              and c["generation"] == 2]
    assert forced and forced[-1]["pause"] > 0
    assert pauses.count(generation=2) >= before + 1
    summary = setup_stage_s.collections_after(forced, since)
    assert summary["by_generation"]["2"]["count"] == len(forced)
    assert summary["longest"][0]["at_s"] >= 0
