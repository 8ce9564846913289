"""Rows that share one streamed expert in a decode step: ``pairs /
experts_hit`` of the decode side of ``engine.snapshot()["moe"]`` between the
window's two snapshots: an expert that is hit is read whole whatever its
rows, so this is what a larger batch or a wider tile changes. Nothing where
the program keeps no such counters or no expert was hit. Source:
program_counter."""


def read(run):
    a = ((run.window.get("snap0") or {}).get("moe") or {}).get("decode")
    b = ((run.window.get("snap1") or {}).get("moe") or {}).get("decode")
    if not a or not b or b["experts_hit"] == a["experts_hit"]:
        return None
    return (b["pairs"] - a["pairs"]) / (b["experts_hit"] - a["experts_hit"])
