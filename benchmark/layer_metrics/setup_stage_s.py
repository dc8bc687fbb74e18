"""Set-up: seconds of one stage of the program's builds
(`setup_stage_s.<stage>.<cell>`: `trace`, `lower`, `compile` or
`first_run`), summed over the FIRST build of each program tag, as the
program's own recorder booked them (`alphafold2_tpu.obs.builds`: a
`FoldExecutor` key such as `fold/640x1/m128/r3`, or `train_step`).

The window runs what set-up built, so a tag's first build is set-up's. A later
build of the same tag is not (`kernel_ms`'s own capture builds the dearest
program again after the window), nor is an untagged build (the weights' draw,
the check's plain reference). A `compile` read from the persistent cache is
the read.

Worked out once a run. One line lists each first build (its stages, cache
state and when it ended), the later builds of each tag, the untagged builds'
seconds by stage, and Python's collections after the last first build: how
many in each generation, their pause, and the five longest with their times
from that build's end. A program without the recorder (a parent commit) gives
nothing."""

import functools

STAGES = ("trace", "lower", "compile", "first_run")


def first_builds(records) -> dict:
    """{tag: {stage: seconds, "cache": ..., "end": ...}} of each tagged
    program's first build."""
    out = {}
    for r in records:
        if not r["tagged"] or r["build"] != 1:
            continue
        build = out.setdefault(r["program"], {"cache": "none", "end": 0.0})
        build[r["stage"]] = build.get(r["stage"], 0.0) + r["end"] - r["start"]
        build["end"] = max(build["end"], r["end"])
        if r["stage"] == "compile":
            build["cache"] = r["cache"]
    return out


def collections_after(collections, since: float) -> dict:
    """Python's collections from `since` on: count and pause by generation,
    the total pause, and the five longest (`at_s` from `since`)."""
    by_gen, total = {}, 0.0
    for c in collections:
        gen = by_gen.setdefault(str(c["generation"]),
                                {"count": 0, "pause_s": 0.0})
        gen["count"] += 1
        gen["pause_s"] += c["pause"]
        total += c["pause"]
    longest = sorted(collections, key=lambda c: -c["pause"])[:5]
    return {"by_generation": by_gen, "pause_s": total,
            "longest": [{"generation": c["generation"], "pause_s": c["pause"],
                         "at_s": c["start"] - since} for c in longest]}


@functools.lru_cache(maxsize=1)
def _stages(run):
    try:
        from alphafold2_tpu.obs import builds
    except ImportError:
        return None
    from benchmark.report import say
    records = builds.records()
    first = first_builds(records)
    later, untagged = {}, dict.fromkeys(STAGES[:3], 0.0)
    for r in records:
        if r["tagged"] and r["build"] > 1 and r["stage"] == "trace":
            later[r["program"]] = later.get(r["program"], 0) + 1
        elif not r["tagged"]:
            untagged[r["stage"]] += r["end"] - r["start"]
    since = max((b["end"] for b in first.values()), default=0.0)
    say(phase="setup_stages",
        builds=[dict({f"{s}_s": b.get(s, 0.0) for s in STAGES},
                     program=tag, cache=b["cache"], end_s=b["end"] - since)
                for tag, b in first.items()],
        later_builds=later, untagged_s=untagged,
        gc=collections_after(builds.collections(since), since))
    return {s: sum(b.get(s, 0.0) for b in first.values()) for s in STAGES}


def read(spans, snapshot, trace, cell):
    stages = _stages(cell["run"])
    if stages is None:
        return None
    return stages[cell["metric"]["name"].split(".")[1]] or None
