"""Layering lints — thin bridge over the graftlint registry.

The four AST walkers that used to live here are now registry rules in
dlrover_tpu/analysis/rules.py (LAYER-001, HOST-001, ALLOC-001,
MESH-001), run by `python -m dlrover_tpu.analysis` and by
tests/test_graftlint.py alongside the newer lock/clock/jit/exception
rules. These tests keep their original names (and their vacuity
guards) so the contracts stay individually addressable:

1. dlrover_tpu/serving/ must not import dlrover_tpu.rl (DEVIATIONS
   §5 — the dependency is one-way).
2. serving/engine.py must not materialize device arrays outside the
   ONE designated fetch helper (`_to_host`) and the host-data paths
   (DEVIATIONS §9 — async dispatch).
3. the engine hot path must not allocate device arrays per step
   (DEVIATIONS §10 — paged layout).
4. serving/ must not construct a raw jax.sharding.Mesh (DEVIATIONS
   §11 — the ONE factory is parallel/mesh.py).
5. serving/engine.py holds ONE decode scan, ONE sampler and no
   adapter twin of a program (plain `ast`, no registry rule: the
   contract is a count, not a confinement).
"""

import ast
import pathlib

import pytest

import dlrover_tpu.models.decode
import dlrover_tpu.serving
from dlrover_tpu.analysis import SourceFile, run_rules, unsuppressed
from dlrover_tpu.analysis.rules import (
    DeviceAllocRule,
    HostCopyRule,
    RawMeshRule,
    RlImportRule,
    class_alloc_sites,
    host_copy_sites,
    raw_mesh_uses,
)

SERVING_DIR = pathlib.Path(dlrover_tpu.serving.__file__).parent
REPO_ROOT = SERVING_DIR.parent.parent


def _serving_sources():
    files = sorted(SERVING_DIR.rglob("*.py"))
    assert files, f"no sources under {SERVING_DIR}"
    return [SourceFile.parse(p, root=REPO_ROOT) for p in files]


def _offenders(rule, sources):
    return [
        f.render()
        for f in unsuppressed(run_rules([rule], files=sources))
        if f.rule_id == rule.id
    ]


def test_serving_never_imports_rl():
    offenders = _offenders(RlImportRule(), _serving_sources())
    assert not offenders, (
        "serving/ must not depend on rl/ (DEVIATIONS §5):\n"
        + "\n".join(offenders)
    )


def test_engine_host_copies_only_in_designated_fetch_helper():
    path = SERVING_DIR / "engine.py"
    src = SourceFile.parse(path, root=REPO_ROOT)
    offenders = _offenders(HostCopyRule(), [src])
    assert not offenders, (
        "engine.py must fetch device arrays only through _to_host "
        "(async dispatch contract, DEVIATIONS §9) — a blocking "
        "np.array/np.asarray/jax.device_get on the step path "
        "re-serializes host and device:\n" + "\n".join(offenders)
    )
    # the lint must actually see the designated helper — if _to_host
    # is renamed this test should fail loudly, not pass vacuously
    assert any(
        owner == "_to_host"
        for _, _, owner in host_copy_sites(src.tree)
    )


def test_engine_hot_path_never_allocates_device_arrays():
    path = SERVING_DIR / "engine.py"
    src = SourceFile.parse(path, root=REPO_ROOT)
    offenders = _offenders(DeviceAllocRule(), [src])
    assert not offenders, (
        "ContinuousBatcher may allocate device arrays only in "
        "__init__/reset — the paged hot path updates page tables "
        "through donated jitted programs, never per-step jnp "
        "constructors:\n" + "\n".join(offenders)
    )
    # vacuity guard: ContinuousBatcher.__init__ DOES allocate (pool/
    # table); if the walker stops seeing those, it stopped seeing
    # anything
    calls = class_alloc_sites(src.tree, "ContinuousBatcher")
    assert any(method == "__init__" for _, _, method, _ in calls)


def test_serving_never_constructs_raw_mesh():
    offenders = _offenders(RawMeshRule(), _serving_sources())
    assert not offenders, (
        "serving/ must build meshes through parallel/mesh.py "
        "(serving_mesh validates tp against devices and KV heads and "
        "owns the axis name decode.py's shardings match):\n"
        + "\n".join(offenders)
    )
    # vacuity guard: the walker must flag the patterns it exists to
    # catch — check against a synthetic offender, not the clean tree
    probe = ast.parse(
        "from jax.sharding import Mesh\n"
        "import jax\n"
        "m = jax.sharding.Mesh(devs, ('tp',))\n"
    )
    assert len(raw_mesh_uses(probe)) == 2


def _engine_tree():
    return ast.parse((SERVING_DIR / "engine.py").read_text())


def _defs(tree, name):
    return [
        n.lineno for n in ast.walk(tree)
        if isinstance(n, ast.FunctionDef) and n.name == name
    ]


def _scan_call_sites(tree):
    return [
        n.lineno for n in ast.walk(tree)
        if isinstance(n, ast.Call)
        and isinstance(n.func, ast.Attribute)
        and n.func.attr in ("scan", "fori_loop", "while_loop")
    ]


def _program_builders(tree):
    return [
        n for n in tree.body
        if isinstance(n, ast.FunctionDef)
        and n.name.startswith("_build_") and n.name.endswith("_program")
    ]


@pytest.mark.parametrize(
    "what", ["scan", "advance", "warp", "no_lora_twin", "two_layouts"]
)
def test_engine_holds_one_decode_loop_and_one_sampler(what):
    """The chunk, interleaved-prefill and speculative builders share
    one decode scan, one post-logits advance and decode.py's one
    warp; adapters are optional operands of their programs. A copy
    of any of them coming back (thirteen scans, three warps, six
    `_lora` programs before this test) fails here. Since PR 43 there
    is ONE more builder with ONE more loop, for a model whose forward
    does not yield a token (generation by diffusion over blocks:
    another state a slot, another thing a dispatch returns), over the
    paged pool only; a third loop, or a second of either, fails."""
    tree = _engine_tree()
    builders = _program_builders(tree)
    assert [b.name for b in builders] == [
        "_build_chunk_program", "_build_diffusion_program",
        "_build_pf_chunk_program", "_build_spec_program",
    ]
    if what == "scan":
        sites = _scan_call_sites(tree)
        assert len(sites) == 2, sites
        owners = [
            n.name for n in tree.body
            if isinstance(n, ast.FunctionDef)
            and any(n.lineno <= s <= n.end_lineno for s in sites)
        ]
        assert owners == ["_decode_scan", "_diffusion_scan"]
    elif what == "advance":
        assert len(_defs(tree, "_advance")) == 1
    elif what == "warp":
        decode_tree = ast.parse(
            pathlib.Path(dlrover_tpu.models.decode.__file__).read_text()
        )
        assert not _defs(tree, "_warp")
        assert len(_defs(decode_tree, "_warp")) == 1
        imported = [
            a.name for n in tree.body if isinstance(n, ast.ImportFrom)
            and n.module == "dlrover_tpu.models.decode" for a in n.names
        ]
        assert "_warp" in imported
        assert not {"_mask_top_k", "_mask_top_p"} & set(imported)
    elif what == "no_lora_twin":
        twins = [
            n.name for n in ast.walk(tree)
            if isinstance(n, ast.FunctionDef)
            and (n.name.endswith("_lora") or n.name.endswith("_lora_fn"))
        ]
        assert not twins, twins
    else:
        for b in builders:
            layouts = (
                ["paged"] if b.name == "_build_diffusion_program"
                else ["dense", "paged"]
            )
            jitted = [
                n.name for n in b.body if isinstance(n, ast.FunctionDef)
                and n.name.startswith("_run_")
            ]
            assert len(jitted) == len(layouts), (b.name, jitted)
            (ret,) = [n for n in b.body if isinstance(n, ast.Return)]
            assert [k.value for k in ret.value.keys] == layouts
