"""The share of decode rows the router sent to the output that computes
nothing: ``skipped / (pairs + skipped)`` of the decode side of
``engine.snapshot()["moe"]`` between the window's two snapshots (a top-1
router makes one pair a routed row, so pairs and skipped rows are the rows
routed): the check on how many rows the expert work of ``decode_step_mfu``
and ``moe_expert_roofline`` may count (they take ``1 / (experts + 1)``).
Nothing where the program's counters have no ``skipped`` (a model every row
of which computes). Source: program_counter."""


def read(run):
    a = ((run.window.get("snap0") or {}).get("moe") or {}).get("decode")
    b = ((run.window.get("snap1") or {}).get("moe") or {}).get("decode")
    if not a or not b or "skipped" not in a or "skipped" not in b:
        return None
    skipped = b["skipped"] - a["skipped"]
    rows = skipped + b["pairs"] - a["pairs"]
    return 100.0 * skipped / rows if rows else None
