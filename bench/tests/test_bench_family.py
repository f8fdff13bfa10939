"""The model family is a property of the configuration.

The harness takes everything that depends on the architecture from the
family module a configuration names under ``"reference"``. Pinned here, as
the harness computed them when it still held the dense GQA family itself:
the weights, the work counts and the program's ``ArchConfig`` of both real
configurations, which the family module must reproduce bit for bit. Then:
a family module that exists only in a temporary bench tree runs a batch and
a coldstart cell end to end, the generator builds any list of layer groups,
and no harness file names a family.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtree
from harness import spec, system, weights, work

SEED = 2**40 + 3
CONFIGS = {"smollm-360m": "smollm-360m.coldstart",
           "yi-9b-l24": "yi-9b-l24.batch"}

# sha256 over each leaf's path, shape, dtype and bytes (``_digest``)
TINY_MAKE = {
    False: "1236409e7c9d4be6aba3ddd8c9f2771881510eafd4959c63294d2215bd06187d",
    True: "c4747bd8e521fe74f75d9699836c8da35c68a2cf2f57fbcc38033a7b2196c0ed",
}
LAYER = {
    ("smollm-360m", 0):
        "7372d5a55699e2307a824116232057a735a08b79b31d57c832d0727629c018b9",
    ("smollm-360m", 31):
        "1ab86c1260980e1fd5cf2e15557035d97a1cd2d62b2de606ad45e18cc6a5497e",
    ("yi-9b-l24", 0):
        "4a860b85f9d4dde68c585695b971b633348f7e5ef70898cbf2a537572c47f21b",
}
SMOLLM_TOP = "3fe88ccf97bf616931b28c95c76163920f2b03f159374e769e6e634a8be970ed"
# yi's top leaves take 7 GB to draw on the CPU: their declaration is pinned
YI_TOP = [("embed", (64000, 4096)), ("final_norm", (4096,)),
          ("lm_head", (4096, 64000))]
# (rows, ctx, step_flops, step_bytes)
WORK = {
    "smollm-360m": [(1, 1, 723640320, 723683200),
                    (4, 1000, 3016949760, 764602240),
                    (16, 8000, 12559319040, 1051322240),
                    (32, 65536, 31205621760, 3407996800)],
    "yi-9b-l24": [(1, 1, 8829403136, 8829468672),
                  (4, 1000, 35709255680, 8878596096),
                  (16, 8000, 144409886720, 9222758400),
                  (32, 65536, 308298121216, 12050898944)],
}
ARCH = {
    "smollm-360m": dict(
        name="smollm-360m", family="dense", num_layers=32, d_model=960,
        num_heads=15, num_kv_heads=5, head_dim=64, d_ff=2560,
        vocab_size=49152, tie_embeddings=True, rope_theta=10000.0,
        norm_eps=1e-05, param_dtype="bfloat16"),
    "yi-9b-l24": dict(
        name="yi-9b-l24", family="dense", num_layers=24, d_model=4096,
        num_heads=32, num_kv_heads=4, head_dim=128, d_ff=11008,
        vocab_size=64000, tie_embeddings=False, rope_theta=10000.0,
        norm_eps=1e-06, param_dtype="bfloat16"),
}


def _digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda x: jax.tree_util.keystr(x[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _dims(config: str):
    return spec.load_cell(CONFIGS[config]).dims


@pytest.mark.parametrize("tied", [False, True])
def test_tiny_weights_are_the_pinned_bits(tied):
    d = dataclasses.replace(spec.dims(benchtree.TINY), tied=tied)
    assert _digest(weights.make(d, SEED)) == TINY_MAKE[tied]


@pytest.mark.parametrize("config,index", sorted(LAYER))
def test_real_layer_is_the_pinned_bits(config, index):
    layer = weights.layer_fn(_dims(config), SEED)(index)
    assert _digest(layer) == LAYER[config, index]


def test_real_top_leaves_are_the_pinned_ones():
    assert _digest(weights.top_of(_dims("smollm-360m"), SEED)) == SMOLLM_TOP
    d = _dims("yi-9b-l24")
    assert list(d.family.top_shapes(d).items()) == YI_TOP
    shapes = jax.eval_shape(lambda k: weights.top(d, k),
                            weights.seed_key(SEED))
    assert {k: (v.shape, v.dtype) for k, v in shapes.items()} == \
        {k: (s, jnp.bfloat16) for k, s in YI_TOP}


@pytest.mark.parametrize("config", sorted(WORK))
def test_work_counts_are_the_pinned_ones(config):
    d = _dims(config)
    got = [(r, c, work.step_flops(d, r, c), work.step_bytes(d, r, c))
           for r, c, _, _ in WORK[config]]
    assert got == WORK[config]


@pytest.mark.parametrize("config", sorted(ARCH))
def test_program_config_is_the_pinned_one(config):
    from repro.configs.base import ArchConfig
    d = _dims(config)
    assert d.family.program_config(config, d) == ARCH[config]
    assert system.arch_config(config, d) == ArchConfig(**ARCH[config])


@pytest.fixture(scope="module")
def own_family(tmp_path_factory):
    """A bench tree whose tiny configuration names a family module that
    exists nowhere else."""
    assert not (benchtree.BENCH / "configs" / "gqa_elsewhere.py").exists()
    return benchtree.make(tmp_path_factory.mktemp("family"),
                          family="gqa_elsewhere")


@pytest.mark.parametrize("workload", ["tiny.batch", "tiny.coldstart"])
def test_family_of_another_tree_runs_end_to_end(own_family, workload):
    from harness.main import measure
    result, cell, _ = measure(workload, 17, 2.0, False, bench=own_family,
                              require_tpu=False, cache=False)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    fam = cell.dims.family
    assert fam.__file__ == str(own_family / "configs" / "gqa_elsewhere.py")
    assert fam is spec.reference_module(cell.config, own_family)


def test_no_harness_file_names_a_family():
    for path in sorted((benchtree.BENCH / "harness").glob("*.py")):
        text = path.read_text()
        assert "dense_gqa" not in text, path.name
        assert not re.search(r"family\s*=\s*['\"]", text), path.name


# -- the generator over layer groups ------------------------------------------

A = {"ln_a": (8,), "wa": (8, 16)}
B = {"wb": (16, 8), "b_norm": (8,), "wc": (4,)}


@dataclasses.dataclass(frozen=True)
class _Sizes:
    family: object
    dtype: str = "bfloat16"


def _two_groups():
    return _Sizes(SimpleNamespace(
        top_shapes=lambda d: {"embed": (32, 8), "final_norm": (8,)},
        layer_groups=lambda d: [("first", 1, A), ("rest", 3, B)]))


def test_two_groups_make_a_tree_of_two_stacks():
    d = _two_groups()
    tree = weights.make(d, SEED)
    assert set(tree) == {"first", "rest", "embed", "final_norm"}
    want = {"first": {k: (1, *s) for k, s in A.items()},
            "rest": {k: (3, *s) for k, s in B.items()},
            "embed": (32, 8), "final_norm": (8,)}
    assert jax.tree.map(lambda a: a.shape, tree) == want
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(tree))
    # layers are numbered globally: the second group starts at layer 1
    key = jax.random.fold_in(weights.seed_key(SEED), 1)
    z = jax.random.normal(jax.random.fold_in(key, 0), B["wb"], jnp.float32)
    assert jnp.array_equal(tree["rest"]["wb"][0],
                           (weights.INIT_STD * z).astype(jnp.bfloat16))
    z = jax.random.normal(jax.random.fold_in(key, 1), B["b_norm"],
                          jnp.float32)
    assert jnp.array_equal(tree["rest"]["b_norm"][0],
                           (1.0 + weights.NORM_STD * z).astype(jnp.bfloat16))


def test_layer_fn_is_the_slice_of_its_group():
    d = _two_groups()
    tree = weights.make(d, SEED)
    layer = weights.layer_fn(d, SEED)
    for index, (group, first) in enumerate(
            [("first", 0), ("rest", 1), ("rest", 1), ("rest", 1)]):
        leaves = layer(index)
        assert set(leaves) == set(tree[group])
        for k, v in leaves.items():
            assert jnp.array_equal(tree[group][k][index - first], v), \
                (index, k)
    with pytest.raises(IndexError):
        layer(4)


@pytest.mark.parametrize("tied", [False, True])
def test_one_group_is_the_dense_tree(tied):
    """The dense family's single group ``"layers"`` gives the tree that one
    uniform stack over ``fold_in(key, l)`` gives."""
    d = dataclasses.replace(spec.dims(benchtree.TINY), tied=tied)
    fam = d.family
    assert [(k, n) for k, n, _ in fam.layer_groups(d)] == \
        [("layers", d.layers)]
    dtype = jnp.dtype(d.dtype)

    def stack(key):
        layers = jax.lax.map(
            lambda i: weights._leaves(jax.random.fold_in(key, i),
                                      fam.layer_shapes(d), dtype),
            jnp.arange(d.layers, dtype=jnp.uint32))
        return {"layers": layers, **weights.top(d, key)}
    want = jax.jit(stack)(weights.seed_key(SEED))
    got = weights.make(d, SEED)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(jnp.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
