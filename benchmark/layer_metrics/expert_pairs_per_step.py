"""Token-expert pairs this chip's experts computed a decode layer-launch:
``pairs / layer_launches`` of the decode side of ``engine.snapshot()["moe"]``
between the window's two snapshots: how loaded the held experts are, to set
beside the deployment's. Nothing where the program keeps no such counters.
Source: program_counter."""


def read(run):
    a = ((run.window.get("snap0") or {}).get("moe") or {}).get("decode")
    b = ((run.window.get("snap1") or {}).get("moe") or {}).get("decode")
    if not a or not b or b["layer_launches"] == a["layer_launches"]:
        return None
    return ((b["pairs"] - a["pairs"])
            / (b["layer_launches"] - a["layer_launches"]))
