"""BENCHMARK.json finds every part by name: each cell's configuration,
traffic and limits files, and a reader for each per-layer metric."""
import importlib
import json
from pathlib import Path

import pytest

from chipbench import arch, weights

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.load(open(ROOT / "BENCHMARK.json"))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    conf = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    cdoc = json.load(open(ROOT / conf["file"]))
    assert cdoc["name"] == conf["name"] and cdoc["source"] == conf["source"]
    # a cut key has its published value and reaches the program
    assert cdoc["reduced"] == conf["reduced"]
    overrides = cdoc["program"].get("overrides", {})
    for key in cdoc["reduced"]:
        assert key in cdoc["published"] and key in overrides
    mix = json.load(open(ROOT / "chipbench" / "traffic"
                         / f"{cell['traffic']}.json"))
    assert mix["kind"] in ("requests", "sessions")
    assert mix["context_cap"] <= (cdoc["engine"]["max_pages_per_seq"]
                                  * cdoc["engine"]["page_size"])
    limits = json.load(open(ROOT / "chipbench" / "limits"
                            / f"{cell['name']}.json"))
    assert limits["served_logit_gap_max"] > 0


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader(metric):
    mod = importlib.import_module(f"chipbench.metrics.{metric['name']}")
    assert callable(mod.read)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def _flat(tree, path=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flat(v, f"{path}/{k}"))
        return out
    return {path: tuple(getattr(tree, "shape", tree))}


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_weights_match_program_layout(conf):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    cdoc = json.load(open(ROOT / conf["file"]))
    want = jax.eval_shape(lambda: M.init(
        arch.load(cdoc).program_config(cdoc), jax.random.PRNGKey(0),
        jnp.bfloat16))
    assert _flat(weights.shapes(cdoc)) == _flat(want)
