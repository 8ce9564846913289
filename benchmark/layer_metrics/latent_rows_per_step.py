"""The context a decode step attends over: live latent rows a decode launch
read, a layer (``latent_rows / layers / launches`` of the decode side of
``engine.snapshot()["mla"]`` between the window's two snapshots; the program
counts a launch's rows times its layers). The decode kernel's time and the
pool's fill both follow it. Nothing where the program keeps no such
counters. Source: program_counter."""


def read(run):
    a = ((run.window.get("snap0") or {}).get("mla") or {}).get("decode")
    b = ((run.window.get("snap1") or {}).get("mla") or {}).get("decode")
    if not a or not b or b["launches"] == a["launches"]:
        return None
    layers = run.size(run.cfg["runner_args"])["model"]["num_hidden_layers"]
    return ((b["latent_rows"] - a["latent_rows"]) / layers
            / (b["launches"] - a["launches"]))
