"""Validated serving configuration + unified engine statistics (DESIGN.md §3.11).

``EngineConfig`` is the single typed surface for every serving knob that used to
live in ``ServeEngine.__init__``'s 20-kwarg sprawl: a frozen dataclass whose
``__post_init__`` holds all cross-field validation (the chunked/paged/
token-budget/speculate checks), so an invalid combination fails the same way
whether it arrives through ``ServeEngine(cfg, params, config=...)``, the legacy
kwarg shim, a JSON file (``from_json``), or a CLI (``add_config_args`` derives
the flag set from the dataclass fields — new fields appear in every CLI
automatically). Model-dependent checks live in
:meth:`EngineConfig.check_model`, called by the engine once it knows the
``ModelConfig``: SSM/hybrid families serve through the continuous slot-table
scheduler like everyone else (DESIGN.md §3.13), and only the combinations that
genuinely cannot work on recurrent state are rejected — each with its own
:class:`UnsupportedModelError` subclass so callers (and the async server's
error mapping) can branch on the reason instead of parsing messages.

``EngineStats`` is the one way to read the engine's statistics: slot
occupancy, prefix hit rate, draft acceptance and tokens per speculative step,
from ``ServeEngine.stats()``, with a stable ``to_dict()`` schema shared by
``benchmarks/serving_bench.py`` and the async server's metrics endpoint.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any, Dict, Optional, Tuple

import numpy as np

#: serving path → QuantContext wiring (DESIGN.md §3.3). ``None`` keeps the legacy
#: behaviour: whatever the params tree + quant config imply, on the jnp ref
#: backend. The engine turns these into QuantContext kwargs; the config only
#: validates membership.
SERVE_PATHS: Dict[Optional[str], Dict[str, Any]] = {
    None: {},
    "fp": {},
    "fake": {},
    "dequant-fp": {"int_exec": "dequant"},
    "fused-int8": {"int_exec": "pallas", "use_pallas": True},
}


# ==========================================================================
# Typed model-compatibility rejections (DESIGN.md §3.13)
# ==========================================================================

class UnsupportedModelError(ValueError):
    """An :class:`EngineConfig` combination this model family cannot serve.

    Subclasses carry the *reason*; all are ``ValueError`` so pre-§3.13
    callers that caught that keep working."""


class SpeculativeStateError(UnsupportedModelError):
    """``speculate > 1`` on an SSM/hybrid family: the recurrence advances
    destructively per scattered token, so rejected draft tokens cannot be
    rewound (DESIGN.md §3.9)."""


class PrefixReuseStateError(UnsupportedModelError):
    """``prefix_reuse`` on a paged SSM/hybrid family: radix reuse restarts a
    prompt from a mid-sequence page boundary, which position-indexed KV pages
    support but a single end-of-prefix state checkpoint does not (DESIGN.md
    §3.8/§3.13)."""


class ChunkedStateError(UnsupportedModelError):
    """``chunked=True`` on an SSM/hybrid family: the packed ragged step
    scatters interleaved chunks of many slots, which needs position-indexed
    cache writes the recurrent state does not have (DESIGN.md §3.10)."""


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Frozen, JSON-serializable serving configuration (DESIGN.md §3.11).

    Required: ``batch_size`` (slot-table width) and ``max_len`` (per-slot cache
    length). Everything else defaults to the dense continuous batcher with
    greedy sampling. ``cache_dtype`` is stored as a canonical dtype *name*
    (``"bfloat16"``) so configs round-trip losslessly through JSON; ``None``
    means "follow the params dtype". ``prefill_buckets`` is a tuple (JSON lists
    convert on the way in).
    """

    batch_size: int
    max_len: int
    eos_id: Optional[int] = None
    path: Optional[str] = None
    kv_cache: str = "fp"
    cache_layout: str = "dense"
    page_size: int = 8
    n_pages: Optional[int] = None
    prefix_reuse: bool = True
    cache_dtype: Optional[str] = None
    scheduler: str = "continuous"
    prefill_buckets: Optional[Tuple[int, ...]] = None
    chunked: bool = False
    token_budget: int = 64
    speculate: int = 1
    drafter_ngram: int = 3
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    sparsity: str = "none"

    def __post_init__(self):
        # normalize before validating: JSON hands lists/np dtypes through the
        # same constructor the engine shim uses
        if self.prefill_buckets is not None:
            object.__setattr__(self, "prefill_buckets",
                               tuple(int(b) for b in self.prefill_buckets))
        if self.cache_dtype is not None:
            object.__setattr__(self, "cache_dtype",
                               np.dtype(self.cache_dtype).name)
        if self.batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {self.batch_size}")
        if self.max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {self.max_len}")
        if self.path not in SERVE_PATHS:
            raise ValueError(f"unknown serving path {self.path!r}; "
                             f"pick one of {sorted(k for k in SERVE_PATHS if k)}")
        if self.kv_cache not in ("fp", "int8"):
            raise ValueError(f"kv_cache must be 'fp' or 'int8', got "
                             f"{self.kv_cache!r}")
        if self.cache_layout not in ("dense", "paged"):
            raise ValueError(f"cache_layout must be 'dense' or 'paged', got "
                             f"{self.cache_layout!r}")
        if self.scheduler not in ("continuous", "grouped"):
            raise ValueError(f"scheduler must be 'continuous' or 'grouped', "
                             f"got {self.scheduler!r}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.cache_layout == "paged" and self.scheduler != "continuous":
            raise ValueError("the paged layout serves through the continuous "
                             "scheduler (the grouped baseline stays dense)")
        if self.speculate < 1:
            raise ValueError(f"speculate must be >= 1, got {self.speculate}")
        if self.chunked:
            if self.cache_layout != "paged":
                raise ValueError("chunked=True needs cache_layout='paged' "
                                 "(chunks scatter through the page table)")
            if self.token_budget < self.batch_size * self.speculate:
                raise ValueError(
                    f"token_budget {self.token_budget} < batch_size*speculate "
                    f"{self.batch_size * self.speculate}: every generating "
                    f"slot's decode row (or draft window) must fit each step")
        if self.sparsity != "none":
            from repro.models.quantize import parse_nm
            parse_nm(self.sparsity)          # raises on malformed N:M
        if self.speculate > 1:
            if self.temperature > 0.0:
                raise ValueError("speculate > 1 requires greedy sampling "
                                 "(temperature <= 0): acceptance is token-"
                                 "exact only under deterministic sampling")
            if self.scheduler != "continuous":
                raise ValueError("speculate > 1 requires the continuous "
                                 "scheduler (per-slot draft windows)")

    # ----------------------------------------------------------- model checks

    def check_model(self, cfg) -> None:
        """Model-dependent validation the pure config cannot do (§3.13).

        SSM / hybrid families serve continuous, paged, sharded and grouped
        exactly like attention families — only the combinations their
        recurrent state genuinely cannot support are rejected, each with a
        typed :class:`UnsupportedModelError` subclass per reason."""
        stateful = cfg.family in ("ssm", "hybrid")
        if not stateful:
            return
        if self.speculate > 1:
            raise SpeculativeStateError(
                f"speculate > 1 cannot serve family {cfg.family!r}: the SSM "
                f"recurrence cannot rewind rejected draft tokens (§3.9)")
        if self.cache_layout == "paged" and self.prefix_reuse:
            raise PrefixReuseStateError(
                f"radix prefix reuse cannot serve family {cfg.family!r}: a "
                f"state checkpoint cannot restart a prompt from a mid-"
                f"sequence page boundary — pass prefix_reuse=False (§3.13)")
        if self.chunked:
            raise ChunkedStateError(
                f"chunked serving cannot serve family {cfg.family!r}: packed "
                f"ragged chunks need position-indexed cache writes, which "
                f"the recurrent state does not have (§3.10)")

    # ------------------------------------------------------------------- JSON

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        if d["prefill_buckets"] is not None:
            d["prefill_buckets"] = list(d["prefill_buckets"])
        return d

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @classmethod
    def from_dict(cls, d: dict) -> "EngineConfig":
        return cls.from_kwargs(**d)

    @classmethod
    def from_json(cls, blob) -> "EngineConfig":
        """Build from a JSON string / parsed dict. Round-trip lossless:
        ``EngineConfig.from_json(cfg.to_json()) == cfg``."""
        if isinstance(blob, str):
            blob = json.loads(blob)
        return cls.from_dict(blob)

    @classmethod
    def from_kwargs(cls, **kw) -> "EngineConfig":
        """The legacy-kwarg shim's constructor: reject unknown keys with the
        TypeError a direct ``ServeEngine(**kw)`` call used to raise."""
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(kw) - fields)
        if unknown:
            raise TypeError(f"unknown engine config field(s): {unknown}; "
                            f"valid fields: {sorted(fields)}")
        return cls(**kw)


# ==========================================================================
# CLI derivation: flags come from the dataclass fields, not hand-kept lists
# ==========================================================================

#: fields whose argparse help benefits from a one-liner; anything not listed
#: still gets a flag (the point: new config fields appear in every CLI
#: automatically, DESIGN.md §3.11)
_FIELD_HELP = {
    "batch_size": "slot-table width (concurrent sequences)",
    "max_len": "per-slot KV cache length",
    "eos_id": "EOS token id; default: no EOS (token 0 is PAD)",
    "path": "integer execution backend (DESIGN.md §3.3)",
    "kv_cache": "decode K/V storage: fp or int8 codes + per-token scales",
    "cache_layout": "dense slot table (§3.6) or paged pool + radix reuse (§3.8)",
    "page_size": "tokens per KV page (paged layout)",
    "n_pages": "page-pool capacity; default batch_size*max_len/page_size",
    "prefix_reuse": "radix prefix reuse on the paged layout",
    "cache_dtype": "fp KV-cache dtype name; default: params dtype",
    "scheduler": "continuous (slot refill mid-decode) or grouped baseline",
    "prefill_buckets": "comma-separated padded-prefill lengths",
    "chunked": "chunked prefill + prefill-decode interleaving (§3.10)",
    "token_budget": "per-step token budget for chunked serving",
    "speculate": "draft-window size K for speculative decoding (§3.9)",
    "drafter_ngram": "max n-gram length of the prompt-lookup drafter",
    "temperature": "sampling temperature; 0 = greedy",
    "top_k": "top-k sampling cutoff; 0 = disabled",
    "seed": "sampling PRNG seed",
    "sparsity": "N:M structured weight sparsity applied at engine build (§3.12)",
}

_FIELD_CHOICES = {
    "path": [p for p in SERVE_PATHS if p],
    "kv_cache": ["fp", "int8"],
    "cache_layout": ["dense", "paged"],
    "scheduler": ["continuous", "grouped"],
    "sparsity": ["none", "2:4", "4:8"],
}


def _base_type(f: dataclasses.Field):
    t = f.type if not isinstance(f.type, str) else f.type
    s = str(t)
    for name, py in (("int", int), ("float", float), ("bool", bool),
                     ("str", str)):
        if name in s:
            return py
    return str


def add_config_args(parser: argparse.ArgumentParser,
                    prefix: str = "") -> None:
    """Add one ``--<field>`` flag per :class:`EngineConfig` field (underscores
    become dashes). Every flag defaults to *unset* so layering works:
    ``--config file.json`` values win unless the flag is given explicitly
    (:func:`config_from_args`). Bools get ``--x/--no-x`` pairs."""
    group = parser.add_argument_group("engine config (serving/config.py)")
    for f in dataclasses.fields(EngineConfig):
        flag = f"--{prefix}{f.name.replace('_', '-')}"
        helptext = _FIELD_HELP.get(f.name, f.name)
        ftype = _base_type(f)
        if ftype is bool:
            group.add_argument(flag, default=None, help=helptext,
                               action=argparse.BooleanOptionalAction)
        elif f.name == "prefill_buckets":
            group.add_argument(flag, default=None, metavar="B1,B2,...",
                               type=lambda s: tuple(int(x)
                                                    for x in s.split(",")),
                               help=helptext)
        else:
            group.add_argument(flag, default=None, type=ftype,
                               choices=_FIELD_CHOICES.get(f.name),
                               help=helptext)


def config_from_args(args: argparse.Namespace,
                     base: Optional[EngineConfig] = None,
                     **defaults) -> EngineConfig:
    """Layer CLI flags over ``base`` (usually ``--config file.json``) over
    ``defaults`` (the calling script's choices) to build the final config.
    Only flags the user actually passed override the layers below."""
    merged: Dict[str, Any] = dict(defaults)
    if base is not None:
        merged.update(base.to_dict())
    for f in dataclasses.fields(EngineConfig):
        v = getattr(args, f.name, None)
        if v is not None:
            merged[f.name] = v
    return EngineConfig.from_kwargs(**merged)


# ==========================================================================
# Unified engine statistics
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class EngineStats:
    """One snapshot of a ``ServeEngine``'s derived rates + raw counters.

    The derived fields are rates over the engine's raw counters; ``counters``
    is a copy of that dict. ``to_dict()`` flattens both into the stable
    schema ``serving_bench`` rows and the async server's ``metrics()``
    endpoint share — derived rates first, counters after, all floats/ints.
    """

    occupancy: float
    prefix_hit_rate: float
    accept_rate: float
    tokens_per_step: float
    counters: Dict[str, int]

    def to_dict(self) -> dict:
        return {"occupancy": self.occupancy,
                "prefix_hit_rate": self.prefix_hit_rate,
                "accept_rate": self.accept_rate,
                "tokens_per_step": self.tokens_per_step,
                **self.counters}

    @classmethod
    def from_counters(cls, counters: Dict[str, int],
                      batch_size: int) -> "EngineStats":
        c = dict(counters)
        steps = c.get("decode_steps", 0)
        occ = c.get("active_slot_steps", 0) / (steps * batch_size) if steps else 0.0
        prompt = c.get("prompt_tokens", 0)
        hit = c.get("prefix_tokens_reused", 0) / prompt if prompt else 0.0
        drafted = c.get("spec_drafted", 0)
        acc = c.get("spec_accepted", 0) / drafted if drafted else 0.0
        sss = c.get("spec_slot_steps", 0)
        tps = c.get("spec_emitted", 0) / sss if sss else 0.0
        return cls(occupancy=occ, prefix_hit_rate=hit, accept_rate=acc,
                   tokens_per_step=tps, counters=c)
