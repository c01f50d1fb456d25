"""The seam between the engine and the models (ISSUE 30): imports point one
way, the two cached-attention families share one adapter body and differ
only where they must, and the options nobody set are gone. And the seam
around both (ISSUE 46): the program imports nothing that is built on it,
and a record at the root has a reader."""

import ast
import dataclasses
import pathlib

import jax
import jax.numpy as jnp
import pytest

from vtpu.models import ModelConfig, init_params
from vtpu.models.moe import MoEConfig, init_moe_params
from vtpu.serving import ServingConfig
from vtpu.serving.adapters import MoeSlotModel, TransformerSlotModel

VTPU = pathlib.Path(__file__).resolve().parents[1] / "vtpu"


def _imported(path: pathlib.Path) -> set:
    """Every module or name *path* imports, at any depth of nesting, as
    absolute dotted names."""
    package = (("vtpu",) + path.relative_to(VTPU).parts[:-1]
               if path.is_relative_to(VTPU) else ())
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = list(package[:len(package) - node.level + 1]
                        if node.level else ())
            module = ".".join(base + ([node.module] if node.module else []))
            out.add(module)
            out.update(f"{module}.{alias.name}" for alias in node.names)
    return out


ARROWS = {
    "models": ("models/*.py", "vtpu.serving"),
    "ops": ("ops/*.py", "vtpu.serving"),
    "adapters": ("serving/adapters.py", "vtpu.serving.engine"),
}


@pytest.mark.parametrize("package", ARROWS)
def test_imports_point_one_way(package):
    """The models and the kernels know nothing of serving, and the adapters
    nothing of the engine that drives them."""
    pattern, above = ARROWS[package]
    files = sorted(VTPU.glob(pattern))
    assert files
    upward = {
        str(path.relative_to(VTPU)): sorted(
            name for name in _imported(path)
            if name == above or name.startswith(above + "."))
        for path in files}
    assert not {path: names for path, names in upward.items() if names}


MOVED = ["batched_decode_step", "batched_spec_step",
         "chunked_prefill_into_slot", "_chunk_window", "_chunk_write_back",
         "_scatter_prefill_pages", "prefill_into_slot", "prefill_into_slots"]


@pytest.mark.parametrize("name", MOVED)
def test_step_function_has_one_home(name):
    """A step function lives in vtpu/models/slots.py: the engine holds no
    second name for it, and no file of the repo asks the engine for it."""
    from vtpu.models import slots
    from vtpu.serving import engine

    assert callable(getattr(slots, name))
    assert not hasattr(engine, name)
    root = VTPU.parent
    asking = [
        str(path.relative_to(root)) for path in sorted(root.rglob("*.py"))
        if not any(part.startswith(".")
                   for part in path.relative_to(root).parts)
        and f"vtpu.serving.engine.{name}" in _imported(path)]
    assert not asking


ROOT = VTPU.parent
BUILT_ON_THEM = {"benchmarks", "hack", "bench", "tests"}


def test_the_product_and_the_harness_import_nothing_built_on_them():
    """``vtpu/`` and ``vbench/`` stand on their own: a script, a study or a
    test is built on them and never the other way round, or deleting one
    would break the program (PR 46 deleted thirteen)."""
    files = sorted(VTPU.rglob("*.py")) + sorted((ROOT / "vbench").rglob("*.py"))
    assert files
    wrong = {
        str(path.relative_to(ROOT)): sorted(
            name for name in _imported(path)
            if name.split(".")[0] in BUILT_ON_THEM)
        for path in files}
    assert not {path: names for path, names in wrong.items() if names}


READERS = ("vtpu", "vbench", "tests", "hack", "benchmarks", ".github")
READER_FILES = ("bench.py", "README.md", "ROADMAP.md", "PERF.md")


def test_every_record_at_the_root_has_a_reader():
    """A ``*.json`` at the root is named by a file that could open it: one
    that nothing names is evidence for nothing, which is how nineteen of
    them came to sit there until PR 46."""
    records = sorted(path.name for path in ROOT.glob("*.json"))
    assert records
    texts = [(ROOT / name).read_text() for name in READER_FILES]
    texts += [
        path.read_text(errors="ignore")
        for top in READERS for path in sorted((ROOT / top).rglob("*"))
        if path.is_file() and path.suffix != ".json"
        and "__pycache__" not in path.parts]
    unread = [r for r in records if not any(r in text for text in texts)]
    assert not unread, (
        f"{unread}: named by nothing under {READERS} nor by {READER_FILES}; "
        f"give the record a reader or take it out")


DENSE = ModelConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32,
    head_dim=16, dtype=jnp.float32, use_pallas=False)
MOE = MoEConfig(
    vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=32, n_experts=4,
    top_k=2, max_seq=32, head_dim=16, dtype=jnp.float32)
SHARED = ["init_state", "decode_step", "spec_step", "prefill_into_slot",
          "prefill_into_slots", "prefill_chunk_into_slot"]
STATED = ["_shard", "_prefill_fn", "_ffn"]


@pytest.fixture(scope="module")
def models():
    return (
        TransformerSlotModel(init_params(jax.random.key(0), DENSE), DENSE),
        MoeSlotModel(init_moe_params(jax.random.key(0), MOE), MOE))


@pytest.mark.parametrize("method", SHARED)
def test_two_families_share_one_adapter_body(models, method):
    dense, moe = models
    assert getattr(type(dense), method) is getattr(type(moe), method)


@pytest.mark.parametrize("method", STATED)
def test_a_family_states_what_differs(method):
    assert (getattr(TransformerSlotModel, method)
            is not getattr(MoeSlotModel, method))


def test_whole_prompt_forwards_keep_their_shortcuts(models):
    """One dense row runs the model function's own forward; a dense batch
    gathers each row's last position before the vocabulary projection
    ([N, vocab]); the expert family returns every position and masks
    routing by the true lengths, for one row and for N."""
    tokens = jnp.zeros((2, 8), jnp.int32)
    lens = jnp.array([3, 8], jnp.int32)
    dense, moe = models
    assert dense._prefill_fn(lens[0]) is None
    forward = dense._prefill_fn(lens)
    rows, _ = jax.eval_shape(
        lambda p, t: forward(p, DENSE, t), dense.params, tokens)
    assert rows.shape == (2, DENSE.vocab)
    for true_lens, n in ((lens[0], 1), (lens, 2)):
        forward = moe._prefill_fn(true_lens)
        rows, _ = jax.eval_shape(
            lambda p, t: forward(p, MOE, t), moe.params, tokens[:n])
        assert rows.shape == (n, 8, MOE.vocab)
    assert dense._ffn() is None and callable(moe._ffn())


@pytest.mark.parametrize(
    "option", ["kv_read_buckets", "decode_unroll", "kv_swap_stage_blocks"])
def test_serving_config_has_no_option_nobody_set(option):
    with pytest.raises(TypeError, match=option):
        ServingConfig(**{option: None})
    assert len(dataclasses.fields(ServingConfig)) == 35
