"""Tokens generated per engine step over the window: the difference of
``tokens_generated`` over that of ``steps`` between two
``ServingEngine.snapshot()`` calls. Source: program_counter."""


def read(run):
    a, b = run.window.get("snap0"), run.window.get("snap1")
    if not a or not b or b["steps"] == a["steps"]:
        return None
    return ((b["tokens_generated"] - a["tokens_generated"])
            / (b["steps"] - a["steps"]))
