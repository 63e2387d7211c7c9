"""ISSUE 46: ``models/cached.py`` is the one home of the cached forward, and
a family reaches it (and nothing of a sibling's) by public names at module
level.  An ``ast`` walk over ``deepspeed_tpu/models/*.py``: at the parent of
PR 46 seven families imported ``gpt2``'s private helpers from inside their
functions, 31 times — the next architecture cannot hook in sideways
unnoticed."""

import ast
import pathlib

import deepspeed_tpu.models as models

ROOT = pathlib.Path(models.__file__).parent
MODULES = {p.stem: ast.parse(p.read_text())
           for p in sorted(ROOT.glob("*.py")) if p.stem != "__init__"}
FAMILIES = sorted(set(MODULES) - {"cached"})
#: the family-to-family edges that stay: four are inheritance
#: (``MixtralConfig(LlamaConfig)``; ``KimiLinearConfig(MixtralConfig)``, whose
#: latent layers and routed FFN are those two files' — PR 51; Megatron's
#: checkpoints load as GPT-2), and the diffusion pair shares its convolution
#: and group-norm layers; Granite 4.0-H takes ``llama``'s RMSNorm by its
#: public name and nothing else of a sibling's (PR 55); Brumby IS the Qwen3
#: block with another mixer: ``BrumbyConfig(LlamaConfig)`` and ``llama``'s
#: norm, q/k-norm, rotary and head as that module's attributes (PR 57);
#: ``Dots3Config(MixtralConfig)``, whose two latent kinds are ``llama``'s
#: latent attention at sizes of their own and whose routed FFN is
#: ``mixtral``'s (PR 61); ``GlmDsaConfig(Dots3Config)``: GLM-5's every layer
#: is ``dots3``'s full kind (its block, its cache, its forwards by their
#: public names) and its module one more such block, with ``llama``'s norm
#: and latent hook (PR 64); ``zaya`` takes ``llama``'s ``rms_norm`` and
#: ``apply_rope`` (PR 66)
ALLOWED = {("mixtral", "llama"), ("megatron_gpt", "gpt2"), ("unet", "vae"),
           ("kimi_linear", "mixtral"), ("kimi_linear", "llama"),
           ("granite_hybrid", "llama"), ("brumby", "llama"),
           ("dots3", "mixtral"), ("dots3", "llama"),
           ("glm_dsa", "dots3"), ("glm_dsa", "llama"),
           ("zaya", "llama")}


def _sibling_imports(tree):
    """``(sibling module, imported name, node, inside a function)`` of every
    import of another module of ``models/`` (relative, or by the package's
    full name)."""
    inside = {id(n) for f in ast.walk(tree)
              if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda))
              for n in ast.walk(f)}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("deepspeed_tpu.models."):
                    yield a.name.split(".")[2], None, node, id(node) in inside
        if not isinstance(node, ast.ImportFrom):
            continue
        module = node.module or ""
        if node.level == 0 and module.startswith("deepspeed_tpu.models"):
            module = module[len("deepspeed_tpu.models"):].lstrip(".")
        elif node.level != 1:
            continue
        for a in node.names:
            # ``from . import llama`` names the sibling; ``from .llama
            # import x`` a name of its
            sibling, name = (module.split(".")[0], a.name) if module \
                else (a.name, None)
            if sibling in MODULES:
                yield sibling, name, node, id(node) in inside


def _where(stem, node):
    return f"models/{stem}.py:{node.lineno}"


def test_cached_imports_no_family():
    found = [_where("cached", n) + f" imports {sib}"
             for sib, _, n, _ in _sibling_imports(MODULES["cached"])]
    assert not found, found


def test_no_family_imports_a_private_name_of_a_sibling():
    found = [f"{_where(stem, n)} imports {sib}.{name}"
             for stem in FAMILIES
             for sib, name, n, _ in _sibling_imports(MODULES[stem])
             if name and name.startswith("_")]
    assert not found, found


def test_the_family_to_family_imports_are_the_ones_that_stay():
    edges = {(stem, sib) for stem in FAMILIES
             for sib, _, _, _ in _sibling_imports(MODULES[stem])
             if sib != "cached"}
    assert edges == ALLOWED, sorted(edges ^ ALLOWED)


def test_no_sibling_is_imported_inside_a_function_body():
    found = [f"{_where(stem, n)} imports {sib} inside a function"
             for stem in MODULES
             for sib, _, n, local in _sibling_imports(MODULES[stem]) if local]
    assert not found, found


def test_the_window_contract_is_decided_in_one_place():
    """One ``per_row = `` in the package, in ``cached.window``; ``llama.py``
    defines none of the loop it used to lend."""
    hits = [p.name for p in sorted(ROOT.glob("*.py"))
            for line in p.read_text().splitlines() if "per_row = " in line]
    assert hits == ["cached.py"], hits
    defined = {n.name for n in ast.walk(MODULES["llama"])
               if isinstance(n, ast.FunctionDef)} | {
        t.id for n in ast.walk(MODULES["llama"])
        if isinstance(n, ast.Assign) for t in n.targets
        if isinstance(t, ast.Name)}
    assert not defined & {"scan_periods_cached", "KIND_LEAVES",
                          "live_tokens"}


def test_the_state_kinds_families_share_the_kind_and_not_each_other():
    """Two families ride the state kind (PR 51, PR 55): both layer names map
    to the SAME two leaves and the ``slot`` table in ``cached.KIND_LEAVES``,
    neither family imports the other, and the second takes one public name
    of ``llama`` (its RMSNorm) and adds no field to ``LlamaConfig``."""
    from deepspeed_tpu.models import cached
    from deepspeed_tpu.ops import paged_kv

    assert cached.KIND_LEAVES["kda"] == cached.KIND_LEAVES["ssm"] \
        == ("state", "conv", "slot")
    assert set(cached.KIND_LEAVES["kda"][:2]) <= set(paged_kv.STATE_LEAVES)
    names = {(sib, name) for sib, name, _, _
             in _sibling_imports(MODULES["granite_hybrid"])}
    assert names == {("cached", None), ("cached", "live_tokens"),
                     ("cached", "qmm"), ("cached", "scan_periods_cached"),
                     ("llama", "rms_norm")}, names
    assert not any(sib == "granite_hybrid" for stem in FAMILIES
                   for sib, _, _, _ in _sibling_imports(MODULES[stem]))
    llama_fields = {t.target.id for n in ast.walk(MODULES["llama"])
                    if isinstance(n, ast.ClassDef) and n.name == "LlamaConfig"
                    for t in n.body if isinstance(t, ast.AnnAssign)}
    assert not {f for f in llama_fields if "ssm" in f or "multiplier" in f}


def test_a_third_state_family_names_its_own_leaf_beside_the_matrix():
    """PR 57: the state kind's leaves are the matrix a head and what the
    FAMILY keeps beside it (``paged_kv.STATE_COMPANIONS``: a convolution's
    tail, a normaliser) — one table for the engine, the layer loop and the
    families; Brumby takes ``llama``'s block by inheritance and by that
    module's attributes, imports no other family, is imported by none, and
    adds no field to ``LlamaConfig``."""
    from deepspeed_tpu.models import cached
    from deepspeed_tpu.ops import paged_kv

    assert paged_kv.STATE_COMPANIONS == {"kda": "conv", "ssm": "conv",
                                         "power": "z"}
    assert paged_kv.STATE_LEAVES == ("state", "conv", "z")
    for kind, beside in paged_kv.STATE_COMPANIONS.items():
        assert cached.KIND_LEAVES[kind] == ("state", beside, "slot")
    names = {(sib, name) for sib, name, _, _
             in _sibling_imports(MODULES["brumby"])}
    assert names == {("cached", None), ("cached", "qmm"),
                     ("cached", "scan_periods_cached"), ("llama", None)}, names
    assert not any(sib == "brumby" for stem in FAMILIES
                   for sib, _, _, _ in _sibling_imports(MODULES[stem]))
    llama_fields = {t.target.id for n in ast.walk(MODULES["llama"])
                    if isinstance(n, ast.ClassDef) and n.name == "LlamaConfig"
                    for t in n.body if isinstance(t, ast.AnnAssign)}
    assert not {f for f in llama_fields if "power" in f or "gate" in f}
