"""A tower's block comes from its configuration (``"blocks"``, looked up by
``spec.block``) and the ingest loop's modality from its traffic, so a cell
for a tower of another block, or an ingest cell of another modality, is
added files and entries only: shown at smoke widths on the CPU, with the
chip look skipped, in a copy of the tree whose files stay byte for byte as
they were. Today's configurations read as they did before blocks: their
leaves and weight keys, their vision item pool and the FLOPs behind
``ingest.mfu``, ``query.mfu`` and ``query.mfu.fresh`` equal the formulas
the harness had then (written out below)."""
import json

import numpy as np
import pytest

import jax

import control
import run as RUN
import test_chipbench_run as TRUN
from chipbench import counts, data, smoke, spec
from reference import weights as W

SEED = 2 ** 31 + 5151
REAL_CONFIGS = ["recall-imagebind", "clip-vit-l14"]
INGEST_METRICS = ["ingest_items_per_s", "ingest.mfu", "idle_share.ingest",
                  "ingest.tower_ms", "ingest.store_ms", "ingest.crossing_mb"]


def _config(name):
    return spec._load_json(spec.BENCH_DIR / "configs" / f"{name}.json")


# -- the formulas of the harness before blocks ------------------------------

def _leaves_before(t, embed_dim):
    L, d, h, f = t["n_layers"], t["d_model"], t["n_heads"], t["d_ff"]
    hd = d // h
    leaves = {
        "cls": ((1, d), "normal", 0.02),
        "exit_head/norm": ((d,), "ones", 0),
        "exit_head/proj": ((d, embed_dim), "fan_in", d),
        "final_norm": ((d,), "ones", 0),
        "layers/attn/wk": ((L, d, h, hd), "fan_in", d),
        "layers/attn/wo": ((L, h, hd, d), "fan_in", h * hd),
        "layers/attn/wq": ((L, d, h, hd), "fan_in", d),
        "layers/attn/wv": ((L, d, h, hd), "fan_in", d),
        "layers/mlp/w_down": ((L, f, d), "fan_in", f),
        "layers/mlp/w_gate": ((L, d, f), "fan_in", d),
        "layers/mlp/w_up": ((L, d, f), "fan_in", d),
        "layers/norm1": ((L, d), "ones", 0),
        "layers/norm2": ((L, d), "ones", 0),
        "pos": ((t["n_tokens"] + 1, d), "normal", 0.02),
    }
    if t["vocab"]:
        leaves["tok_emb"] = ((t["vocab"], d), "embed", 0.02)
    else:
        leaves["proj_in"] = ((t["d_input"], d), "fan_in", t["d_input"])
    return leaves


def _leaf_order_before(config):
    m = config["model"]
    out = [("", "logit_scale", (), "zeros", 0)]
    for t in sorted(m["towers"], key=lambda t: t["modality"]):
        leaves = _leaves_before(t, m["embed_dim"])
        for name in sorted(leaves, key=lambda n: n.split("/")):
            out.append((t["modality"], name) + leaves[name])
    return out


def _ingest_flops_before(cfg, items, layers):
    t = spec.tower(cfg, "vision")
    per_layer = counts.layer_flops(t["n_tokens"] + 1, t["d_model"], t["d_ff"])
    return (layers * per_layer + items * counts.frontend_flops(t, cfg) +
            items * counts.exit_head_flops(t, cfg))


def _query_flops_before(cfg, queries, refined):
    tt, vt = spec.tower(cfg, "text"), spec.tower(cfg, "vision")
    N = cfg["recall"]["superficial_layers"]
    tower = queries * (tt["n_layers"] * counts.layer_flops(
        tt["n_tokens"] + 1, tt["d_model"], tt["d_ff"]) +
        len(spec.exit_layers(cfg, tt["n_layers"])) *
        counts.exit_head_flops(tt, cfg))
    refine = refined * ((vt["n_layers"] - N) * counts.layer_flops(
        vt["n_tokens"] + 1, vt["d_model"], vt["d_ff"]) +
        counts.exit_head_flops(vt, cfg))
    return tower, refine


# -- parity for today's configurations -------------------------------------

@pytest.mark.parametrize("name", REAL_CONFIGS + ["tiny"])
def test_leaf_order_is_the_one_before_blocks(name):
    cfg = smoke.TINY if name == "tiny" else _config(name)
    assert "blocks" not in cfg
    assert W.leaf_order(cfg, spec.BENCH_DIR) == _leaf_order_before(cfg)


@pytest.mark.parametrize("modality", ["vision", "text"])
def test_weights_are_the_programs_leaf_for_leaf(modality):
    """The shared key split draws every leaf the program draws from the
    same seed (the reference is float32 of the served bfloat16)."""
    from repro.models import imagebind as IB
    cfg = smoke.TINY
    s = spec.arch_spec(cfg)
    prog = jax.jit(IB.mem_init, static_argnums=(1, 2))(   # as served
        jax.random.PRNGKey(SEED), s.model, s.recall)
    tower = prog["towers"][modality]
    ref = W.tower_weights(cfg, SEED, modality, spec.BENCH_DIR)
    flat = {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tower)[0]}
    assert set(flat) == set(ref)
    for name, leaf in flat.items():
        np.testing.assert_array_equal(
            np.asarray(leaf, np.float32), np.asarray(ref[name]), err_msg=name)


@pytest.mark.parametrize("name", REAL_CONFIGS)
def test_vision_item_pool_is_the_one_before(name):
    t = spec.tower(_config(name), "vision")
    got = data.ingest_pool(SEED, 3, t)
    want = data.item_pool(SEED, 3, t["n_tokens"], t["d_input"])
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()


def test_token_pool_is_seeded_ids():
    t = spec.tower(smoke.TINY, "text")
    a = data.ingest_pool(SEED, 64, t)
    assert a.shape == (64, t["n_tokens"]) and a.dtype == np.int32
    assert 0 <= a.min() and a.max() < t["vocab"]
    np.testing.assert_array_equal(a, data.ingest_pool(SEED, 64, t))
    assert not np.array_equal(a, data.ingest_pool(SEED + 1, 64, t))


# engine stats of a window: (items, layers run) for ingest at exit 20,
# (queries, refined states) for the query cells
@pytest.mark.parametrize("workload,stats", [
    ("imagebind.ingest", (2816, 2816 * 20)),
    ("imagebind.recall-fresh", (40 * 64, 40 * 64)),
    ("clip.recall-warm", (660 * 64, 0))])
def test_flops_are_the_ones_before_blocks(workload, stats):
    cell = spec.load_cell(workload)
    loop = spec.loop_class(cell.traffic["loop"])
    if cell.traffic["loop"] == "ingest":
        assert loop.flops(cell, *stats) == _ingest_flops_before(
            cell.config, *stats)
    else:
        assert loop.flops(cell, *stats) == _query_flops_before(
            cell.config, *stats)


def test_every_towers_block_exports_what_the_harness_asks():
    bench = json.loads((spec.CHECKOUT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = _config(c["name"])
        for t in cfg["model"]["towers"]:
            blk = spec.block(cfg, t["modality"])
            for name in ("leaves", "Tower", "layer_flops", "frontend_flops",
                         "exit_head_flops"):
                assert callable(getattr(blk, name)), (c["name"], name)


def test_an_unknown_block_is_refused():
    with pytest.raises(spec.SpecError):
        spec.block(dict(smoke.TINY, blocks={"text": "no-such-block"}),
                   "text")


def test_blocks_stay_out_of_the_programs_config():
    cfg = _config("recall-imagebind")
    assert spec.arch_spec(dict(cfg, blocks={"text": "other"})) == \
        spec.arch_spec(cfg)


# -- cells added as files only ---------------------------------------------

TEXT_INGEST = dict(smoke.TRAFFIC["ingest"], modality="text", exit_layer=8)


def _mirror(bench, wrong: bool = False) -> str:
    """The block ``tower`` under another name, recording each reference
    tower it builds; ``wrong`` puts GELU where the program has SiLU."""
    src = (bench / "reference" / "tower.py").read_text()
    if wrong:
        assert src.count("jax.nn.silu(g)") == 1
        src = src.replace("jax.nn.silu(g)", "jax.nn.gelu(g)")
    return src + '''

BUILT = []
_Block = Tower


class Tower(_Block):
    def __init__(self, config, seed, modality, *, cast="f32"):
        BUILT.append((modality, cast))
        super().__init__(config, seed, modality, cast=cast)
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The tiny tree with, added as files and entries only: a text-ingest
    cell, and two ingest cells whose vision tower is of a block written
    here (the tower block under another name, and one that disagrees with
    the program)."""
    root = tmp_path_factory.mktemp("chipbench-blocks")
    bench = smoke.make_tree(root)
    ingest_limits = (bench / "limits" / "tiny.ingest.json").read_text()
    files = {"traffic/tiny-text-ingest.json": json.dumps(TEXT_INGEST),
             "limits/tiny.text-ingest.json": ingest_limits,
             "reference/mirror.py": _mirror(bench),
             "reference/skewed.py": _mirror(bench, wrong=True)}
    cells = [{"name": "tiny.text-ingest", "config": "tiny",
              "traffic": "tiny-text-ingest", "chips": 1, "why": "test"}]
    configs = []
    for blk in ("mirror", "skewed"):
        cfg = dict(smoke.TINY, name=f"tiny-{blk}", blocks={"vision": blk})
        files[f"configs/tiny-{blk}.json"] = json.dumps(cfg)
        files[f"limits/tiny.{blk}.json"] = ingest_limits
        configs.append({"name": f"tiny-{blk}", "source": "test",
                        "file": f"benchmarks/chip/configs/tiny-{blk}.json",
                        "reduced": [], "why": "test"})
        cells.append({"name": f"tiny.{blk}", "config": f"tiny-{blk}",
                      "traffic": "tiny-ingest", "chips": 1, "why": "test"})
    before = TRUN._add_files((root, bench), files,
                             {"configs": configs, "workloads": cells})
    # the new cells report the ingest metrics: their entries list them
    b = json.loads((root / "BENCHMARK.json").read_text())
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in INGEST_METRICS:
            m["workloads"] = m["workloads"] + [c["name"] for c in cells]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    yield root, bench
    TRUN._unchanged(before)


def _run(tree, workload, trace=0):
    root, bench = tree
    return RUN.run(["--workload", workload, "--seed", str(SEED),
                    "--seconds", "0.2", "--trace", str(trace)],
                   root=root, bench_dir=bench, require_tpu=False,
                   peaks=smoke.CPU_PEAKS)


@pytest.fixture
def engines(tree, monkeypatch):
    """(engine handed to the ingest loop, engine it drains), per run."""
    cls = spec.loop_class("ingest", bench_dir=tree[1])
    seen, orig = [], cls.build

    def build(self, engine, query):
        orig(self, engine, query)
        seen.append((engine, self.engine))
    monkeypatch.setattr(cls, "build", build)
    return seen


def test_vision_ingest_drains_the_services_own_engine(tree, engines):
    assert _run(tree, "tiny.ingest")["correct"]
    (given, drained), = engines
    assert drained is given and given.modality == "vision"


def test_text_ingest_is_correct_and_reports_the_ingest_metrics(
        tree, engines, monkeypatch):
    res = _run(tree, "tiny.text-ingest")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["compilations_in_window"] == 0
    assert set(res["metrics"]) == {"ingest_items_per_s", "setup_s"}
    (given, drained), = engines
    assert drained.modality == "text" and drained.store is given.store
    assert drained.stats.n_embedded > 0 and given.stats.n_embedded == 0
    # traced: the CPU trace has no device plane, so one op is put over the
    # whole window; the span readers read the program's own spans
    from chipbench import trace as TR
    orig = TR.from_xplane

    def with_device(trace_dir):
        tr = orig(trace_dir)
        lo, hi = TR.window_of(tr)
        tr["device"]["/device:CPU:0"] = [["op", lo, hi - lo]]
        return tr
    monkeypatch.setattr(TR, "from_xplane", with_device)
    res = _run(tree, "tiny.text-ingest", trace=1)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for name in ("ingest.tower_ms", "ingest.crossing_mb", "ingest.mfu"):
        assert m[name]["value"] > 0, name


@pytest.mark.parametrize("fault", [TRUN._altered_continuation,
                                   TRUN._half_batch])
def test_broken_text_ingest_is_not_correct(tree, monkeypatch, fault):
    fault(monkeypatch)
    assert not _run(tree, "tiny.text-ingest")["correct"]


def test_text_ingest_control_fails_its_limits(tree):
    cell = spec.load_cell("tiny.text-ingest", root=tree[0],
                          bench_dir=tree[1])
    assert control.judge(cell, SEED)["correct"] is False


def test_check_and_control_use_the_configurations_block(tree):
    root, bench = tree
    mirror = spec._module("reference", "mirror", bench)
    res = _run(tree, "tiny.mirror")
    assert res["correct"], res["checks"]
    assert mirror.BUILT == [("vision", "f32")]
    cell = spec.load_cell("tiny.mirror", root=root, bench_dir=bench)
    assert control.judge(cell, SEED)["correct"] is False
    assert mirror.BUILT[1:] == [("vision", "f32"), ("vision", "fp8")]


def test_a_block_that_disagrees_with_the_program_is_not_correct(tree):
    skewed = spec._module("reference", "skewed", tree[1])
    res = _run(tree, "tiny.skewed")
    assert skewed.BUILT == [("vision", "f32")]
    assert not res["correct"]
    assert res["checks"]["sup_gap"]["value"] > \
        res["checks"]["sup_gap"]["limit"]
