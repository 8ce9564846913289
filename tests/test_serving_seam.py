"""The seam between the serving models and the engine: the model modules
import the lane's shared module and no sibling, and what a model counts
reaches ``ServingEngine.snapshot()`` and ``/serving`` by the model's own
names and lines, whatever they are."""

import ast
import os
import threading
import types

import pytest

import brpc_tpu.serving as serving
from brpc_tpu.serving import (EngineConfig, KVCacheConfig, ModelConfig,
                              PagedKVCache, ServingEngine, TinyTransformer)

SERVING = os.path.dirname(serving.__file__)
# the modules that hold a model, and what each may import of the others:
# the hybrid lane's shared module (hybrid_model, which holds SambaY too),
# and, for the toy block's mesh form, the block it shards
MODELS = sorted(f[:-3] for f in os.listdir(SERVING)
                if f == "model.py" or f.endswith("_model.py"))
BASES = {"mesh_model": {"hybrid_model", "model"}}


def _imported(name):
    """The ``brpc_tpu.serving`` modules module ``name`` imports from."""
    with open(os.path.join(SERVING, name + ".py")) as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith("brpc_tpu.serving."):
                out.add(node.module.split(".")[2])
            elif node.module == "brpc_tpu.serving":
                out.update(a.name for a in node.names)
        elif isinstance(node, ast.Import):
            out.update(a.name.split(".")[2] for a in node.names
                       if a.name.startswith("brpc_tpu.serving."))
    return out


def test_every_hybrid_model_is_a_model_module():
    assert {"hybrid_model", "moe_model", "jamba_model", "zaya_model",
            "glm_model", "model", "mesh_model"} <= set(MODELS)


@pytest.mark.parametrize("name", MODELS)
def test_a_model_module_imports_no_sibling_model(name):
    allowed = BASES.get(name, {"hybrid_model"}) - {name}
    siblings = (_imported(name) & set(MODELS)) - allowed
    assert not siblings, f"{name} imports {sorted(siblings)}"


# ------------------------------------------------------------ the counters
class _Probed(TinyTransformer):
    """A model with a counter family no engine or service names."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.probe_counters = {"steps": 0, "rows": {"seen": 0}}

    def decode_step(self, tokens, positions, tables):
        self.probe_counters["steps"] += 1
        self.probe_counters["rows"]["seen"] += len(tokens)
        return super().decode_step(tokens, positions, tables)

    def counters(self):
        return dict(super().counters(), probe=self.probe_counters)

    def counter_lines(self, snap):
        p = snap["probe"]
        return super().counter_lines(snap) + [
            f"  probe: steps={p['steps']} rows={p['rows']['seen']}"]


@pytest.fixture(scope="module")
def probed():
    cfg = ModelConfig(vocab=64, d_model=32, n_heads=2, n_layers=1,
                      attn="reference")
    kv = PagedKVCache(KVCacheConfig(block_size=16, num_blocks=32),
                      cfg.n_layers, cfg.kv_dim)
    model = _Probed(cfg, kv)
    eng = ServingEngine(model, kv, EngineConfig(
        max_batch=2, token_budget=128, idle_wait_s=0.002)).start()
    evs = []
    for plen in (9, 21):
        ev = threading.Event()
        code, _ = eng.submit(model.synth_prompt(plen), 5,
                             done=lambda _r, ev=ev: ev.set())
        assert code == 0
        evs.append(ev)
    assert all(ev.wait(120) for ev in evs)
    yield eng, model
    eng.stop()
    model.close()
    kv.close()


def test_a_family_the_engine_never_heard_of_is_in_the_snapshot(probed):
    eng, model = probed
    snap = eng.snapshot()
    assert snap["probe"] == model.probe_counters
    assert snap["probe"]["steps"] > 0
    assert snap["probe"]["rows"]["seen"] >= snap["probe"]["steps"]
    # the families the engine always carries: this model's own, and None
    assert snap["decode"]["decode_launches_gather"] > 0
    assert snap["moe"] is None and snap["scan"] is None \
        and snap["mla"] is None


def test_the_snapshot_copies_what_the_model_counts(probed):
    eng, model = probed
    snap = eng.snapshot()
    seen = snap["probe"]["rows"]["seen"]
    model.probe_counters["rows"]["seen"] += 1000
    try:
        assert snap["probe"]["rows"]["seen"] == seen
        assert eng.snapshot()["probe"]["rows"]["seen"] == seen + 1000
    finally:
        model.probe_counters["rows"]["seen"] -= 1000


def test_serving_prints_the_models_own_lines(probed):
    from brpc_tpu.builtin.services import serving_service

    eng, model = probed
    status, _ctype, text = serving_service(
        None, types.SimpleNamespace(query={}, path="/serving"))
    assert status == 200
    lines = text.splitlines()
    probe = [l for l in lines if l.startswith("  probe: ")]
    assert probe and f"steps={model.probe_counters['steps']}" in probe[-1]
    # right after the model's decode line, as the model formats them
    assert lines[lines.index(probe[-1]) - 1].startswith("  decode: ")
