"""The share of this chip's held experts a decode layer-launch reached:
distinct held experts hit a layer-launch over the experts held, from the
decode side of ``engine.snapshot()["moe"]`` between the window's two
snapshots: the share of the expert weights a step must stream, and the check
on the expectation ``1 - (1 - 1/held)^pairs`` that ``moe_expert_roofline`` and
``decode_step_mfu`` count bytes by. Nothing where the program keeps no such
counters. Source: program_counter."""


def read(run):
    moe0 = (run.window.get("snap0") or {}).get("moe") or {}
    moe1 = (run.window.get("snap1") or {}).get("moe") or {}
    a, b = moe0.get("decode"), moe1.get("decode")
    if not a or not b or b["layer_launches"] == a["layer_launches"]:
        return None
    return (100.0 * (b["experts_hit"] - a["experts_hit"])
            / (b["layer_launches"] - a["layer_launches"])
            / moe1["experts_held"])
