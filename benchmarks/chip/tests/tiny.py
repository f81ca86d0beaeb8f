"""Each cell at a size the CPU runs in seconds: what the tests override,
and the cells the tests run that ``BENCHMARK.json`` does not list yet."""
import json
from pathlib import Path

HELD_BACK = Path(__file__).resolve().parent / "held_back.json"

TINY = {
    "tgv256-dns": {"config": {"grid": [16, 16, 16], "dt": 0.01}},
    "tgv768-x4": {"config": {"grid": [16, 16, 16], "dt": 0.01}},
    "ghia-sweep-backlog": {
        "config": {"grid": [16, 16, 4], "slots": 8,
                   "check": {"members": 8}},
        "traffic": {"members": 64, "steps": 16, "budget": 16}},
    "ghia-served-poisson": {
        "config": {"grid": [16, 16, 4], "slots": 4,
                   "check": {"members": 8}},
        "traffic": {"rate_per_s": 150, "steps_min": 4, "steps_max": 32}},
}
SEED = 2 ** 31 + 12345


def overrides(workload: str) -> dict:
    """The tiny override of ``workload``, its check merged into the
    committed configuration's check (whose limits it keeps)."""
    import run

    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, _ = run.cell_files(bench, workload)
    tiny = TINY[workload]
    cfg = dict(tiny.get("config", {}))
    cfg["check"] = {**config["check"], **cfg.get("check", {})}
    return {**tiny, "config": cfg}


def with_held_back(bench: dict) -> dict:
    """``bench`` with the entries of ``held_back.json`` merged in; an entry
    whose name ``bench`` has already adds its workloads to that one."""
    held = json.loads(HELD_BACK.read_text())
    out = dict(bench)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"]: dict(e) for e in bench[key]}
        for e in held.get(key, []):
            if e["name"] in have:
                have[e["name"]]["workloads"] = (
                    have[e["name"]]["workloads"] + e["workloads"])
            else:
                have[e["name"]] = e
        out[key] = list(have.values())
    return out


def patch_harness(setattr) -> None:
    """Make the harness read ``BENCHMARK.json`` with the held-back cells."""
    import run

    load = run.load_json
    setattr(run, "load_json", lambda path: with_held_back(load(path))
            if path.name == "BENCHMARK.json" else load(path))
