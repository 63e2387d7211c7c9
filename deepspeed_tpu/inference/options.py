"""What a :class:`~deepspeed_tpu.inference.serving.ServingEngine` may be
built as, said ONCE.

The constructor, ``init_serving``, ``resolved_config()`` and the
autotuner's space (``autotuning/space.py``) all read these tables; none of
them states an option's name, its default, a range or a combination of its
own.  This module imports nothing of ``jax``, ``serving.py`` or
``autotuning/``: it is read before any backend exists.

 - :data:`OPTIONS`: every keyword option, its default, its range and the
   attribute of a built engine that holds its resolved value.
 - :data:`FEATURES`: what a construction can ASK FOR, each named once — the
   name ``stats()[...]["refused"]`` publishes, the label an error carries
   and whether these options and mesh degrees ask for it.
 - :data:`EXCLUDES`: the rules between options, each with the sentence its
   ``ValueError`` carries, grouped under the name the autotuner's pruning
   report counts them by.
 - :data:`KIND_REFUSES`: what a cache kind (a decode hook of the model) is
   not served with, feature -> reason, and :data:`KIND_SAYS`, the sentence
   that opens a kind's refusal.
 - :func:`check`: the one function that raises for all of it.

Adding a cache kind's list of what it is not served with is one entry of
:data:`KIND_REFUSES`; removing an option is its row of :data:`OPTIONS` and
its mentions below, beside its own code.
"""

from __future__ import annotations

import copy
import dataclasses
import inspect
from operator import attrgetter
from types import SimpleNamespace
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

__all__ = ["OPTIONS", "FEATURES", "EXCLUDES", "KIND_REFUSES", "KIND_SAYS",
           "VERIFY_T_MAX", "QUANT_MODES", "SELF_DRAFT", "Option", "Feature",
           "Rule",
           "bind", "signature", "resolved", "parse_quantize", "check_ranges",
           "check", "violations"]

#: the verify kernel's widest speculative window (K + 1 <= this): the value
#: of ``ops/decode_attention.VERIFY_T_MAX``, stated here because this module
#: imports no jax (``tests/unit/test_serving_options.py`` holds the two equal)
VERIFY_T_MAX = 16
#: ``draft=SELF_DRAFT`` (beside ``spec_tokens=K``): the proposer is the
#: served model's OWN multi-token-prediction module (decode hook
#: ``self_draft``: ``{"depth", "layers", "cache", "forward"}``) — no second
#: model, no second pool: the module's rows are more layers of the target's
#: own leaves under the target's tables, its draft stays on the device beside
#: the pending token, and a round is one harvest.  Stated here, once: every
#: other value of ``draft`` is a second MODEL
SELF_DRAFT = "self"
#: legal ``quantize=`` values (order-normalized; ``None`` = full precision)
QUANT_MODES = ("kv8", "w8a8", "w8a8+kv8")


def parse_quantize(quantize):
    """Normalize the ``quantize=`` knob -> ``(normalized str | None,
    kv_quant bool, want_w8a8 bool)``; raises naming the legal values."""
    if quantize is None or quantize == "":
        return None, False, False
    parts = sorted(str(quantize).split("+"))
    if not set(parts) <= {"kv8", "w8a8"} or len(set(parts)) != len(parts):
        raise ValueError(
            f"quantize={quantize!r} — expected one of {QUANT_MODES} "
            "(or None for full precision)")
    norm = "+".join(p for p in ("w8a8", "kv8") if p in parts)
    return norm, "kv8" in parts, "w8a8" in parts


@dataclasses.dataclass(frozen=True)
class Option:
    """One keyword option: its ``default``; ``valid`` (a predicate over a
    given value; None: any) with ``expect``, the words after "must be";
    ``attr``, the (dotted) attribute of a built engine that holds the
    resolved value ``resolved_config()`` reports (None: not captured)."""
    default: Any
    attr: Optional[str]
    valid: Optional[Callable[[Any], bool]] = None
    expect: str = ""


def _at_least(n):
    return {"valid": lambda v: int(v) >= n, "expect": f">= {n}"}


def _one_of(*values):
    words = ", ".join(map(repr, values[:-1])) + f" or {values[-1]!r}"
    return {"valid": lambda v: v in values, "expect": words}


#: the options of ``ServingEngine(engine, **options)`` and of
#: ``init_serving(..., **options)``, in the order their signatures show them
#: (what each means: the constructor's docstring)
OPTIONS: Dict[str, Option] = {
    "slots": Option(8, "slots", **_at_least(1)),
    "max_seq_len": Option(None, "max_seq_len"),
    "prefill_batch": Option(4, "prefill_batch", **_at_least(1)),
    "block_size": Option(None, "block_size",
                         lambda v: v is None or int(v) >= 1, ">= 1"),
    "num_blocks": Option(None, "_alloc.num_blocks"),
    "prefill_chunk": Option(128, "prefill_chunk"),
    "prefix_caching": Option(None, "prefix_caching"),
    "engine_mode": Option("replicas", "engine_mode",
                          **_one_of("replicas", "dp_tp")),
    "sp": Option(1, "sp_degree", **_at_least(1)),
    "resident_window_blocks": Option(0, "resident_window_blocks",
                                     **_at_least(0)),
    "spec_tokens": Option(0, "spec_tokens", **_at_least(0)),
    "quantize": Option(None, "quantize"),
    "host_blocks": Option(0, "host_blocks", **_at_least(0)),
    "swap_batch": Option(8, "swap_batch"),
    "role": Option("both", "role", **_one_of("prefill", "decode", "both")),
    "nvme_blocks": Option(0, "nvme_blocks", **_at_least(0)),
    "nvme_high_watermark": Option(0.9, "nvme_high_watermark",
                                  lambda v: 0.0 < float(v) <= 1.0,
                                  "in (0, 1]"),
    # the path as given (None = a tempfile of the engine's own): a rebuilt
    # engine mints its OWN spill file rather than contending for this one's
    "nvme_path": Option(None, "_nvme_path_arg"),
    # a model object: not captured (a draft-model engine round-trips to
    # the n-gram proposer at the same ``spec_tokens``; ``SELF_DRAFT``, a
    # word, is what ``resolved_config()`` adds for an engine built with it)
    "draft": Option(None, None),
    "ngram_max": Option(3, "ngram_max"),
    "ngram_min": Option(1, "ngram_min"),
    "shard_kv": Option(None, "kv_sharded"),
    "sampling": Option(True, "sampling"),
    "logit_masks": Option(False, "logit_masks"),
    "debug_checks": Option(False, "debug_checks"),
    # ``telemetry/trace.py DEFAULT_CAPACITY`` (the test holds them equal)
    "trace_capacity": Option(131072, "timeline.capacity"),
    "slo_targets": Option(None, "_slo.targets"),
    "peak_flops": Option(None, "peak_flops"),
}


def signature(*leading: inspect.Parameter, var_keyword: Optional[str] = None
              ) -> inspect.Signature:
    """``leading`` parameters, then every option keyword-only at its
    default (then ``**var_keyword``): what ``inspect.signature`` shows of a
    callable that takes its options as ``**given``."""
    params = list(leading) + [
        inspect.Parameter(name, inspect.Parameter.KEYWORD_ONLY,
                          default=opt.default)
        for name, opt in OPTIONS.items()]
    if var_keyword:
        params.append(inspect.Parameter(var_keyword,
                                        inspect.Parameter.VAR_KEYWORD))
    return inspect.Signature(params)


def bind(given: Mapping[str, Any], callee: str) -> SimpleNamespace:
    """``given`` over the defaults, by attribute; a keyword that is no
    option is the ``TypeError`` any unknown keyword is."""
    for name in given:
        if name not in OPTIONS:
            raise TypeError(
                f"{callee}() got an unexpected keyword argument {name!r}")
    return SimpleNamespace(**{
        name: _as(opt.default, given.get(name, opt.default))
        for name, opt in OPTIONS.items()})


def _as(default, value):
    """``value`` in its default's type (``slots="4"`` is 4); an option
    whose default is None, and a None, stay as given."""
    return value if default is None or value is None \
        else type(default)(value)


def resolved(engine) -> Dict[str, Any]:
    """Every captured option's resolved value, read off a built engine
    (a copy: the engine's own tables are not handed out)."""
    return {name: copy.deepcopy(attrgetter(opt.attr)(engine))
            for name, opt in OPTIONS.items() if opt.attr}


@dataclasses.dataclass(frozen=True)
class Feature:
    """Something a construction asks for: ``label`` (a format over the
    options and degrees) is how an error names it, ``asked`` whether these
    options and degrees ask for it."""
    label: str
    asked: Callable[[SimpleNamespace], Any]


#: by the name ``stats()[...]["refused"]`` publishes.  Over the options as
#: given plus the degrees: ``tp`` / ``dp`` (the engine mesh's; ``dp`` is 1
#: outside ``engine_mode="dp_tp"``), ``weights`` (the wrapped engine's
#: weight quantization type, None for float weights), ``kv8`` / ``w8a8``
#: (what ``quantize`` parses to)
FEATURES: Dict[str, Feature] = {
    "prefix_caching": Feature("prefix_caching=True",
                              lambda a: a.prefix_caching),
    "host_blocks": Feature("host_blocks={host_blocks}",
                           lambda a: int(a.host_blocks)),
    "nvme_blocks": Feature("nvme_blocks={nvme_blocks}",
                           lambda a: int(a.nvme_blocks)),
    "spec_tokens": Feature("spec_tokens={spec_tokens}",
                           lambda a: int(a.spec_tokens)),
    "a draft model": Feature(
        "a draft model",
        lambda a: a.draft is not None and a.draft != SELF_DRAFT),
    "quantize": Feature("quantize='kv8'", lambda a: a.kv8),
    "quantized weights": Feature("quantized weights ({weights})",
                                 lambda a: a.weights),
    "resident_window_blocks": Feature(
        "resident_window_blocks", lambda a: int(a.resident_window_blocks)),
    "a tp mesh": Feature("a tp mesh (tp={tp})", lambda a: a.tp > 1),
    "engine_mode": Feature("engine_mode='dp_tp' (dp={dp})",
                           lambda a: a.dp > 1),
    "sp": Feature("sp={sp}", lambda a: int(a.sp) > 1),
}

#: how each kind's refusal opens (``{model}``: the model's name)
KIND_SAYS: Dict[str, str] = {
    "state": "{model} keeps a recurrent state a slot (decode hook "
             "state_layers), which is not served with ",
    "tails": "{model} makes its keys by causal convolutions and keeps their "
             "tails a slot beside the paged pool (decode hook tail_layers), "
             "which is not served with ",
    "window": "{model} mixes sliding-window and full attention layers "
              "(decode hook window_layers): its pool holds a second kind of "
              "block under a ring table of its own, which is not served "
              "with ",
    "indexer": "{model} selects its keys with a learned indexer (decode hook "
               "sparse_attention), which is served on one shard over a float "
               "pool; not with ",
    "latent": "{model} caches a latent a token (decode hook "
              "latent_attention): its pool is one leaf without a head axis, "
              "read absorbed, which is not served with ",
}

_TIERS = "the tiers move blocks; a slot's state has none"
#: kind -> feature -> why (None: the kind's sentence says it), in the order
#: the refusals are checked and published
KIND_REFUSES: Dict[str, Dict[str, Optional[str]]] = {
    "state": {
        "prefix_caching": "a state can be shared only where it was "
                          "snapshotted, not at any block boundary",
        "host_blocks": _TIERS,
        "nvme_blocks": _TIERS,
        "spec_tokens": "a rejected draft token has already moved the state: "
                       "\"rollback is free\" holds for keys and values only",
        "a draft model": "a rejected draft token has already moved the "
                         "state",
        "quantize": "the state is float32 by construction",
        "quantized weights": "the state kind's leaves (decays, convolution "
                             "taps, gates) have no int8 record",
        "resident_window_blocks": "a window slides over blocks; the state "
                                  "has none",
        "a tp mesh": "the state's heads are not sharded: one shard",
        "engine_mode": "the state's rows are not sharded: one shard",
        "sp": "the chunked recurrence carries its state along the sequence",
    },
    "tails": {
        "prefix_caching": "the first token after a shared block needs the "
                          "tails at that block's end, and no block keeps them",
        "host_blocks": "a demoted row's blocks would come back without its "
                       "tails",
        "nvme_blocks": "a demoted row's blocks would come back without its "
                       "tails",
        "spec_tokens": "a rejected draft token has already moved the tails",
        "a draft model": "a rejected draft token has already moved the "
                         "tails",
        "quantize": "the tails and the finished keys are float by "
                    "construction",
        "quantized weights": "the convolutions' taps and the router's "
                             "matrices have no int8 record",
        "resident_window_blocks": "a window slides over blocks; the tails "
                                  "have none",
        "a tp mesh": "the grouped convolution's groups are the heads, and "
                     "they are not sharded: one shard",
        "engine_mode": "the tails' rows are not sharded: one shard",
        "sp": "the convolutions carry their tails along the sequence",
    },
    "window": dict.fromkeys((
        "prefix_caching", "host_blocks", "quantize",
        "resident_window_blocks", "spec_tokens", "a draft model",
        "a tp mesh", "engine_mode", "sp")),
    "indexer": {
        **dict.fromkeys(("a tp mesh", "engine_mode", "sp", "quantize",
                         "resident_window_blocks")),
        "a draft model": "a second model; the model's own module, "
                         "draft='self', is served: its index keys are one "
                         "more layer of this leaf"},
    "latent": {
        "quantize": "a quantized latent is a different model: the value is "
                    "a projection of the same vector the key is",
        "quantized weights": "the absorbed read takes kv_b_w as the two "
                             "up-projections it holds, not as an int8 record",
        "a tp mesh": "the latent has no head axis to shard: it is replicated "
                     "under tp by design (head-sharded up-projections around "
                     "a replicated pool), a path that is not built",
        "engine_mode": "the latent write and read run on one shard",
        "sp": "sequence-parallel prefill all-to-alls heads of K and V",
        "a draft model": "a second model's pool would be a second kind "
                         "beside it (the model's own module, draft='self', "
                         "is served: its rows are one more layer of this "
                         "leaf)",
        "resident_window_blocks": "the latent kernel carries no "
                                  "resident-window mask",
    },
}


@dataclasses.dataclass(frozen=True)
class Rule:
    """A combination that is refused: ``group`` (the name the autotuner's
    pruning report counts it under), ``broken`` (whether these options and
    degrees break it) and ``says`` (the ``ValueError``'s sentence)."""
    group: str
    broken: Callable[[SimpleNamespace], Any]
    says: Callable[[SimpleNamespace], str]


def _blocks_for(tokens, block_size):
    return -(-int(tokens) // int(block_size))


def _min_window(a):
    """One prefill chunk's span + 1 decode block."""
    return _blocks_for(a.prefill_chunk, a.block_size) + 1


#: in the order they are checked.  Over the options (``block_size`` /
#: ``prefill_chunk`` / ``prefix_caching`` as the constructor resolved them)
#: plus :data:`FEATURES`' degrees and ``mesh_sp`` (the engine mesh's sp
#: axis).  A rule that needs a pool's leaves or a kernel's plan is the
#: constructor's own
EXCLUDES: Tuple[Rule, ...] = (
    Rule("spec_window",
         lambda a: a.spec_tokens and a.spec_tokens + 1 > VERIFY_T_MAX,
         lambda a: f"spec_tokens={a.spec_tokens} needs a "
         f"{a.spec_tokens + 1}-token verify window but the paged verify "
         f"kernel takes at most {VERIFY_T_MAX} — lower spec_tokens to "
         f"{VERIFY_T_MAX - 1} or less"),
    Rule("draft_needs_spec_tokens",
         lambda a: a.draft is not None and not a.spec_tokens,
         lambda a: "a draft model was given but spec_tokens is 0 — pass "
         "spec_tokens=K to enable speculative decoding"),
    Rule("logit_masks_excludes_dp_tp",
         lambda a: a.logit_masks and not a.sampling,
         lambda a: "logit_masks=True needs the sampling stack — constrained "
         "decoding applies the mask inside the sampler programs; drop "
         "sampling=False"),
    Rule("w8a8_needs_int8_weights",
         lambda a: a.w8a8 and a.weights != "w8a8",
         lambda a: "quantize includes 'w8a8' but the wrapped engine carries "
         f"{a.weights or 'full-precision'} weights — build it with "
         "config={'quant': {'enabled': True, 'type': 'w8a8'}} "
         "(init_serving(quantize=...) does this for you)"),
    Rule("engine_mode_exclusive",
         lambda a: a.engine_mode == "dp_tp" and (
             a.spec_tokens or int(a.host_blocks) or a.quantize),
         lambda a: "engine_mode='dp_tp' v1 excludes speculative decoding, "
         "the host KV tier and quantization — run those compositions in "
         "'replicas' mode"),
    Rule("engine_mode_exclusive",
         lambda a: a.engine_mode == "dp_tp" and a.prefix_caching,
         lambda a: "engine_mode='dp_tp' v1 excludes prefix caching (the "
         "trie would share blocks across dp groups) — pass "
         "prefix_caching=False"),
    Rule("logit_masks_excludes_dp_tp",
         lambda a: a.engine_mode == "dp_tp" and a.logit_masks,
         lambda a: "engine_mode='dp_tp' v1 excludes logit_masks — the "
         "[slots, vocab] mask operand is not dp-sharded yet; run "
         "constrained decoding in 'replicas' mode"),
    Rule("engine_mode_exclusive",
         lambda a: a.slots % a.dp,
         lambda a: f"engine_mode='dp_tp': slots ({a.slots}) must divide "
         f"evenly over the mesh dp axis ({a.dp})"),
    Rule("sp_prefill_exclusive",
         lambda a: a.sp > 1 and a.mesh_sp != a.sp,
         lambda a: f"sp={a.sp} but the engine mesh carries an sp axis of "
         f"size {a.mesh_sp} — build the engine with "
         f"config={{'sequence_parallel': {a.sp}}} "
         "(init_serving(sp=...) does this for you)"),
    Rule("sp_prefill_exclusive",
         lambda a: a.sp > 1 and a.prefill_chunk % a.sp,
         lambda a: f"prefill_chunk ({a.prefill_chunk}) must divide evenly "
         f"over sp={a.sp} — each sp rank owns a prefill_chunk/sp sequence "
         "shard"),
    Rule("sp_prefill_exclusive",
         lambda a: a.sp > 1 and a.dp > 1,
         lambda a: "sp > 1 composes with tp, not with engine_mode='dp_tp' "
         "— run sequence-parallel prefill in 'replicas' mode"),
    Rule("sp_prefill_exclusive",
         lambda a: a.sp > 1 and a.spec_tokens,
         lambda a: "sp > 1 v1 excludes speculative decoding — the "
         f"draft/verify programs are decode-side (T <= {VERIFY_T_MAX}) "
         "where sequence parallelism has nothing to shard; drop "
         "spec_tokens"),
    Rule("resident_window_span",
         lambda a: a.resident_window_blocks and not int(a.host_blocks),
         lambda a: "resident_window_blocks > 0 needs the tiered KV cache "
         "(host_blocks > 0): cold context blocks demote to the host arena "
         "when the window slides past them"),
    Rule("resident_window_span",
         lambda a: a.resident_window_blocks and a.spec_tokens,
         lambda a: "resident_window_blocks > 0 v1 excludes speculative "
         "decoding — the verify window's span math assumes a dense block "
         "table; drop spec_tokens"),
    Rule("resident_window_span",
         lambda a: a.resident_window_blocks and a.dp > 1,
         lambda a: "resident_window_blocks > 0 v1 excludes engine_mode="
         "'dp_tp' — run resident-window serving in 'replicas' mode"),
    Rule("resident_window_span",
         lambda a: a.resident_window_blocks and a.sp > 1,
         lambda a: "resident_window_blocks > 0 v1 excludes sp > 1 — "
         "sequence-parallel prefill assumes every committed block is "
         "device-resident; pick one per engine"),
    Rule("resident_window_span",
         lambda a: a.resident_window_blocks
         and a.resident_window_blocks < _min_window(a),
         lambda a: f"resident_window_blocks ({a.resident_window_blocks}) "
         f"must be >= {_min_window(a)} (one prefill_chunk span + 1 decode "
         "block) or the window would slide out from under the chunk "
         "currently being prefilled"),
    Rule("swap_batch_bounds",
         lambda a: a.host_blocks and a.swap_batch < 1,
         lambda a: f"swap_batch must be >= 1, got {a.swap_batch}"),
    Rule("swap_batch_bounds",
         lambda a: a.host_blocks and a.swap_batch > a.host_blocks,
         lambda a: f"swap_batch={a.swap_batch} exceeds host_blocks="
         f"{a.host_blocks} — one demotion batch could never fit the host "
         "arena; lower swap_batch or grow host_blocks"),
    Rule("tiered_needs_prefix_cache",
         lambda a: a.host_blocks and not a.prefix_caching,
         lambda a: "the tiered KV cache (host_blocks > 0) needs "
         "prefix_caching=True — promoted chains re-register in the prefix "
         "trie (drop prefix_caching=False, or host_blocks)"),
    Rule("role_needs_tiered_kv",
         lambda a: a.role != "both" and not a.host_blocks,
         lambda a: f"role={a.role!r} needs the tiered KV cache "
         "(host_blocks > 0): the prefill→decode handoff travels as a "
         "host-tier chain export/import — pass host_blocks, or role='both'"),
    Rule("nvme_needs_host_tier",
         lambda a: a.nvme_blocks and not a.host_blocks,
         lambda a: f"nvme_blocks={a.nvme_blocks} needs the host tier above "
         "it (host_blocks > 0) — NVMe entries spill from and promote "
         "through the host arena, never the device pool directly"),
    Rule("nvme_watermark_window",
         lambda a: a.nvme_blocks and a.swap_batch > int(
             a.nvme_high_watermark * a.host_blocks),
         lambda a: f"swap_batch={a.swap_batch} exceeds the host-arena "
         f"watermark budget int({a.nvme_high_watermark} * {a.host_blocks}) "
         "— one promotion batch would immediately re-spill its own head; "
         "lower swap_batch or raise nvme_high_watermark/host_blocks"),
    Rule("self_draft",
         lambda a: a.draft == SELF_DRAFT and a.logit_masks,
         lambda a: "draft='self' excludes logit_masks — a round commits up "
         "to spec_tokens + 1 tokens on the device and the mask row is built "
         "on the host a token at a time; drop logit_masks, or draft"),
    Rule("self_draft",
         lambda a: a.draft == SELF_DRAFT and int(a.host_blocks),
         lambda a: "draft='self' excludes the tiered KV cache (host_blocks) "
         "— the module's entry at a position is made from the NEXT token, "
         "so a demoted chain's last block is not keyed by its own tokens; "
         "drop host_blocks, or draft"),
)


def check_ranges(options: Mapping[str, Any]) -> None:
    """Every option inside its own range (:data:`OPTIONS`), whatever the
    others say: what the constructor needs before it can size anything."""
    for name, opt in OPTIONS.items():
        value = options.get(name, opt.default)
        if opt.valid is not None and not opt.valid(value):
            raise ValueError(f"{name} must be {opt.expect}, got {value!r}")
    parse_quantize(options.get("quantize"))


def _asked(options, degrees) -> SimpleNamespace:
    """What the rules and features read: the options (one that is not
    given, at its default), the degrees, and what ``quantize`` parses to."""
    _, kv8, w8a8 = parse_quantize(options.get("quantize"))
    return SimpleNamespace(**{
        **{name: opt.default for name, opt in OPTIONS.items()},
        **options, **degrees, "kv8": kv8, "w8a8": w8a8})


def violations(options: Mapping[str, Any], degrees: Mapping[str, Any]):
    """``(group, sentence)`` of every rule of :data:`EXCLUDES` these
    options and degrees break, in order (an iterator: the first is what
    :func:`check` raises)."""
    a = _asked(options, degrees)
    return ((rule.group, rule.says(a)) for rule in EXCLUDES
            if rule.broken(a))


def check(options: Mapping[str, Any], degrees: Mapping[str, Any],
          kinds: Sequence[str] = (), model_name: str = "<model>"
          ) -> Dict[str, list]:
    """Raise the ``ValueError`` of the first thing these ``options`` (every
    name of :data:`OPTIONS`) may not be built as on a mesh of these
    ``degrees`` (``tp``, ``dp``, ``mesh_sp``, ``weights``) for a model with
    the cache ``kinds`` (keys of :data:`KIND_REFUSES`): an option out of
    its range, everything a kind refuses that was asked for (one error a
    kind, each item by its label and reason), a rule of :data:`EXCLUDES`.
    -> ``{kind: the names it refuses}``, what ``stats()`` publishes."""
    check_ranges(options)
    a = _asked(options, degrees)
    refusals = {}
    for kind in (k for k in KIND_REFUSES if k in kinds):
        refuses = KIND_REFUSES[kind]
        refusals[kind] = list(refuses)
        unserved = [
            FEATURES[name].label.format(**vars(a))
            + (f" ({why})" if why else "")
            for name, why in refuses.items() if FEATURES[name].asked(a)]
        if unserved:
            sep = "; " if any(refuses.values()) else ", "
            raise ValueError(KIND_SAYS[kind].format(model=model_name)
                             + sep.join(unserved))
    for _, says in violations(options, degrees):
        raise ValueError(says)
    return refusals
