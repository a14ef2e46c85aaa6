"""Model registry for the multi-model gateway: resident alpha banks.

The paper's premise — weights regenerated on the fly from small alpha banks
— makes multi-model serving cheap where dense serving is not: what has to
stay resident per model is the compressed alpha coefficients (plus the
shared basis indices), a fraction of one dense weight copy. This module
owns that residency:

* :class:`ModelRegistry` — named entries (config + a ``loader`` that can
  re-materialise the params bit-identically, e.g. a checkpoint restore or a
  seeded init), grouped by architecture signature. Residency is **group**
  granular: a group of same-architecture variants serves from ONE stacked
  engine, so its members load and evict together.
* **Byte budget + LRU eviction** — ``ensure_resident_group`` loads a group
  and, while the ledger exceeds ``budget_bytes``, evicts the
  least-recently-used *unpinned* group (in-flight requests pin their
  model's group). The ledger counts stacked sharing once: each resident
  model is charged its alpha bank; the shared non-alpha leaves (embeddings,
  norms, dense projections, basis indices) are charged once per group —
  exactly the footprint of the stacked pytree the engine holds.
* :class:`VariantSet` / :func:`stack_variants` — stack same-architecture
  params into one pytree where ONLY the alpha leaves (``alphas`` /
  ``alphas_q8`` / ``alphas_q4`` / ``alpha_scale``) carry a leading variant
  axis; every other leaf is verified bit-equal and shared. The stacked
  pytree feeds ``LLMEngine(variants=M, model_index=vset.index)``.
* :func:`make_alpha_variant` — derive a same-architecture variant by
  deterministically perturbing ONLY the alpha banks (the "fine-tune
  touched the alphas" story), guaranteed stackable with its source.
* **Integrity scrub** — registration/load captures a CRC32-per-leaf ledger
  of the alpha bank (:func:`alpha_crc_ledger`); :meth:`ModelRegistry.scrub`
  re-checksums a resident entry against it, and
  :meth:`ModelRegistry.repair_group` re-materialises a corrupted group from
  its loaders, *verifying* the reload is bitwise what the ledger recorded.
  Because only compressed coefficients are resident, a scrub pass and a
  repair cost kilobytes-to-megabytes — the paper's memory-wall trick doing
  double duty as a reliability trick.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

# Leaves that differ between same-architecture variants (per-model state);
# everything else — dense weights, norms, embeddings, basis indices — is
# shared and must be bit-equal for variants to stack into one engine.
_STACK_KEYS = ("alphas", "alphas_q8", "alphas_q4", "alpha_scale")
# Leaves that constitute the compressed representation the paper keeps
# resident (coefficients + scales + basis indices).
_ALPHA_BANK_KEYS = _STACK_KEYS + ("idx",)


def _path_leaf_key(path) -> str:
    """Last dict key of a tree path ('' for non-dict e.g. list indices)."""
    if not path:
        return ""
    return str(getattr(path[-1], "key", ""))


def param_bytes(params: Any) -> int:
    """Total bytes of a params pytree (host/device agnostic)."""
    return sum(int(np.dtype(l.dtype).itemsize) * int(np.size(l))
               for l in jax.tree_util.tree_leaves(params))


def alpha_bank_bytes(params: Any) -> int:
    """Bytes of the compressed per-model state: alpha coefficients /
    quantised alphas + scales + basis indices."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return sum(int(np.dtype(l.dtype).itemsize) * int(np.size(l))
               for path, l in flat
               if _path_leaf_key(path) in _ALPHA_BANK_KEYS)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _alpha_bank_leaves(params: Any) -> list:
    """``(path_str, leaf)`` for every alpha-bank leaf, in flatten order —
    the deterministic leaf indexing shared by the CRC ledger, ``scrub``,
    and the ``flip`` fault injector."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(_path_str(path), leaf) for path, leaf in flat
            if _path_leaf_key(path) in _ALPHA_BANK_KEYS]


def alpha_crc_ledger(params: Any) -> dict:
    """CRC32 per alpha-bank leaf (path string -> checksum of raw bytes).
    The integrity ground truth captured at load time; cheap because only
    the compressed representation is covered."""
    return {p: zlib.crc32(np.asarray(leaf).tobytes())
            for p, leaf in _alpha_bank_leaves(params)}


def dense_fp32_bytes(cfg: ModelConfig) -> int:
    """Bytes of ONE dense-fp32 copy of this architecture (OVSF disabled) —
    the memory-wall baseline the gateway's resident-bytes gate compares
    against. Computed from shape specs only (no allocation)."""
    from repro.models import registry as R
    dense = cfg.replace(ovsf=dataclasses.replace(cfg.ovsf, enable=False),
                        exec_plan=None)
    return R.param_count_from_specs(R.model_init_specs(dense)) * 4


def arch_signature(cfg: ModelConfig) -> str:
    """Architecture identity ignoring the display name and the (per-engine)
    execution plan: two configs with the same signature produce
    structurally identical param pytrees and can share a stacked engine."""
    return repr(cfg.replace(name="", exec_plan=None))


def make_alpha_variant(params: Any, seed: int, scale: float = 0.05) -> Any:
    """Derive a same-architecture variant by deterministically perturbing
    ONLY the alpha banks: float alphas get a per-leaf scalar factor;
    quantised banks get the factor on ``alpha_scale`` (the packed integer
    codes keep their storage format). Codes (``idx``) and every
    dense/norm/embedding leaf are untouched, so the result stacks with its
    source (:func:`stack_variants`)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    base = jax.random.PRNGKey(seed)
    out = []
    for i, (path, leaf) in enumerate(flat):
        key = _path_leaf_key(path)
        if key in ("alphas", "alpha_scale"):
            factor = 1.0 + scale * jax.random.normal(
                jax.random.fold_in(base, i), ())
            out.append((leaf * factor.astype(jnp.float32)).astype(leaf.dtype))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


@dataclasses.dataclass(frozen=True)
class VariantSet:
    """Same-architecture variants stacked for one multi-model engine:
    ``params`` carries a leading ``M`` axis on exactly the alpha leaves;
    ``index(name)`` is the variant row a request's model name routes to."""
    names: tuple
    cfg: ModelConfig
    params: Any
    M: int

    def index(self, name: Optional[str]) -> int:
        if name is None:
            return 0
        return self.names.index(name)


def stack_variants(named_params: list, cfg: ModelConfig) -> VariantSet:
    """Stack ``[(name, params), ...]`` into a :class:`VariantSet`.

    Alpha leaves (``_STACK_KEYS``) gain a variant axis; every other leaf
    must be bit-equal across members (shared basis indices included — the
    multi kernel applies ONE spectral transform and routes per-token through
    the stacked coefficients) and is stored once.

    Axis placement: leaves under ``blocks`` are scan-stacked with a leading
    ``n_layers`` axis, so the variant axis goes at position 1 — the per-block
    scan slice then yields the (M, ...) leaf ``ovsf_matmul_multi`` expects.
    Leaves outside ``blocks`` get a leading variant axis.
    """
    if len(named_params) < 2:
        raise ValueError("stack_variants needs >= 2 members; a single model "
                         "serves from a plain LLMEngine")
    names = tuple(n for n, _p in named_params)
    flats = []
    treedef0 = None
    for n, p in named_params:
        flat, treedef = jax.tree_util.tree_flatten_with_path(p)
        if treedef0 is None:
            treedef0 = treedef
        elif treedef != treedef0:
            raise ValueError(f"variant {n!r} has a different param structure "
                             "— not the same architecture")
        flats.append(flat)
    leaves = []
    for i, (path, first) in enumerate(flats[0]):
        key = _path_leaf_key(path)
        rows = [flat[i][1] for flat in flats]
        if key in _STACK_KEYS:
            axis = 1 if _path_leaf_key(path[:1]) == "blocks" else 0
            leaves.append(jnp.stack(rows, axis=axis))
        else:
            for n, r in zip(names[1:], rows[1:]):
                if not np.array_equal(np.asarray(first), np.asarray(r)):
                    pstr = "/".join(str(getattr(k, "key", k)) for k in path)
                    raise ValueError(
                        f"variant {n!r} differs from {names[0]!r} on shared "
                        f"leaf {pstr!r}; only alpha banks may differ between "
                        "stacked variants")
            leaves.append(first)
    params = jax.tree_util.tree_unflatten(treedef0, leaves)
    return VariantSet(names=names, cfg=cfg, params=params,
                      M=len(named_params))


@dataclasses.dataclass
class ModelEntry:
    """One registered model: how to (re)load it, and its residency state."""
    name: str
    cfg: ModelConfig
    loader: Callable[[], Any]       # re-materialises params bit-identically
    tags: tuple = ()
    group: str = ""                 # arch signature (set by the registry)
    params: Any = None              # None = evicted
    bytes: int = 0                  # resident param bytes (whole pytree)
    alpha_bytes: int = 0            # resident alpha-bank bytes
    last_used: int = 0              # monotonic request sequence (not wall
                                    # time: deterministic LRU under test)
    pinned: int = 0                 # in-flight requests (eviction guard)
    loads: int = 0
    evictions: int = 0
    # integrity scrub state: CRC32 per alpha-bank leaf, captured at FIRST
    # load (the bitwise ground truth every reload must reproduce)
    crc_ledger: dict = dataclasses.field(default_factory=dict)
    scrubs: int = 0                 # scrub passes over this entry
    corruptions: int = 0            # scrubs that found a CRC mismatch
    repairs: int = 0                # verified bitwise re-residencies

    @property
    def resident(self) -> bool:
        return self.params is not None


class ModelRegistry:
    """Named model store with a byte budget and group-granular LRU."""

    def __init__(self, budget_bytes: Optional[int] = None):
        self.entries: dict[str, ModelEntry] = {}
        self.budget_bytes = budget_bytes
        self._seq = 0

    # -- registration / lookup --------------------------------------------

    def register(self, name: str, cfg: ModelConfig,
                 loader: Callable[[], Any], tags: tuple = ()) -> ModelEntry:
        if name in self.entries:
            raise ValueError(f"model {name!r} already registered")
        e = ModelEntry(name=name, cfg=cfg, loader=loader, tags=tuple(tags),
                       group=arch_signature(cfg))
        self.entries[name] = e
        return e

    def get(self, name: Optional[str]) -> Optional[ModelEntry]:
        if name is None:
            return None
        return self.entries.get(name)

    def names(self) -> list:
        return list(self.entries)

    def groups(self) -> dict:
        """group signature -> member names, in registration order."""
        out: dict[str, list] = {}
        for n, e in self.entries.items():
            out.setdefault(e.group, []).append(n)
        return out

    def group_members(self, group: str) -> list:
        return [n for n, e in self.entries.items() if e.group == group]

    # -- LRU / pinning ------------------------------------------------------

    def touch(self, name: str) -> None:
        self._seq += 1
        self.entries[name].last_used = self._seq

    def pin(self, name: str) -> None:
        self.entries[name].pinned += 1

    def unpin(self, name: str) -> None:
        e = self.entries[name]
        e.pinned = max(0, e.pinned - 1)

    def group_pinned(self, group: str) -> int:
        return sum(self.entries[n].pinned for n in self.group_members(group))

    # -- byte ledger --------------------------------------------------------

    def resident_bytes(self) -> int:
        """Ledger of resident bytes with stacked sharing counted once: every
        resident model is charged its alpha bank; the shared (non-alpha)
        leaves are charged once per group — the footprint of the stacked
        pytree the group's engine actually holds."""
        total = 0
        seen: set = set()
        for e in self.entries.values():
            if not e.resident:
                continue
            total += e.alpha_bytes
            if e.group not in seen:
                total += e.bytes - e.alpha_bytes
                seen.add(e.group)
        return total

    # -- residency ----------------------------------------------------------

    def _load(self, e: ModelEntry) -> None:
        e.params = e.loader()
        e.bytes = param_bytes(e.params)
        e.alpha_bytes = alpha_bank_bytes(e.params)
        e.loads += 1
        if not e.crc_ledger:    # first load: capture the integrity ledger
            e.crc_ledger = alpha_crc_ledger(e.params)

    # -- integrity scrub ----------------------------------------------------

    def scrub(self, name: str) -> list:
        """Re-checksum one resident entry's alpha bank against its ledger.
        Returns the corrupted leaf paths ([] = clean or not resident)."""
        e = self.entries[name]
        if not e.resident:
            return []
        e.scrubs += 1
        current = alpha_crc_ledger(e.params)
        bad = [p for p, crc in e.crc_ledger.items()
               if current.get(p) != crc]
        bad += [p for p in current if p not in e.crc_ledger]
        if bad:
            e.corruptions += 1
        return bad

    def corrupt(self, name: str, leaf: int = 0, bit: int = 0) -> str:
        """Flip one bit of alpha-bank leaf index ``leaf`` (flatten order,
        wrapped) in the resident params — the ``flip`` fault injector.
        Dtype-agnostic: the flip lands in the leaf's raw byte buffer, so
        fp32, int8, and packed int4 banks are all fair game. Returns the
        corrupted leaf's path."""
        e = self.entries[name]
        if not e.resident:
            raise ValueError(f"model {name!r} is not resident")
        flat, treedef = jax.tree_util.tree_flatten_with_path(e.params)
        bank = [i for i, (path, _l) in enumerate(flat)
                if _path_leaf_key(path) in _ALPHA_BANK_KEYS]
        i = bank[leaf % len(bank)]
        path, old = flat[i]
        raw = np.asarray(old)
        buf = bytearray(raw.tobytes())
        b = (bit // 8) % len(buf)
        buf[b] ^= 1 << (bit % 8)
        new = np.frombuffer(bytes(buf), raw.dtype).reshape(raw.shape)
        leaves = [l for _p, l in flat]
        leaves[i] = jnp.asarray(new)
        e.params = jax.tree_util.tree_unflatten(treedef, leaves)
        return _path_str(path)

    def repair(self, name: str) -> None:
        """Re-materialise one entry from its loader and VERIFY the reload
        is bitwise what the ledger recorded at first load — a repair that
        silently changed the bank would corrupt token streams instead of
        fixing them. Raises RuntimeError when the source itself no longer
        matches (checkpoint rot: operator intervention required)."""
        e = self.entries[name]
        fresh = e.loader()
        if alpha_crc_ledger(fresh) != e.crc_ledger:
            raise RuntimeError(
                f"repair of {name!r} failed verification: the loader no "
                "longer reproduces the registered alpha bank bitwise")
        e.params = fresh
        e.bytes = param_bytes(fresh)
        e.alpha_bytes = alpha_bank_bytes(fresh)
        e.loads += 1
        e.repairs += 1

    def repair_group(self, group: str) -> list:
        """Bitwise re-residency of every resident member of ``group``
        (stacked variants rebuild together). Returns the repaired names."""
        done = []
        for n in self.group_members(group):
            if self.entries[n].resident:
                self.repair(n)
                done.append(n)
        return done

    def unregister(self, name: str) -> ModelEntry:
        """Remove a model (hot REMOVE). Refuses while requests are in
        flight — the caller drains first."""
        e = self.entries[name]
        if e.pinned:
            raise RuntimeError(
                f"model {name!r} has {e.pinned} in-flight request(s)")
        e.params = None
        del self.entries[name]
        return e

    def evict_group(self, group: str, on_evict: Optional[Callable] = None
                    ) -> None:
        """Drop a group's params (its engine serves no in-flight work — the
        caller checked pins). ``on_evict(group)`` lets the gateway drop the
        corresponding engine."""
        for n in self.group_members(group):
            e = self.entries[n]
            if e.resident:
                e.params = None
                e.evictions += 1
        if on_evict is not None:
            on_evict(group)

    def _lru_group(self, exclude: str) -> Optional[str]:
        """Least-recently-used evictable group: resident, unpinned, not the
        requesting group. Recency of a group = its most recent member."""
        cands = []
        for g, members in self.groups().items():
            if g == exclude:
                continue
            if not any(self.entries[n].resident for n in members):
                continue
            if self.group_pinned(g):
                continue
            cands.append((max(self.entries[n].last_used for n in members), g))
        if not cands:
            return None
        return min(cands)[1]

    def ensure_resident_group(self, group: str,
                              on_evict: Optional[Callable] = None) -> bool:
        """Make every member of ``group`` resident, evicting LRU unpinned
        groups while the ledger exceeds the budget. Returns False — with the
        group rolled back to evicted — when the budget cannot be met (the
        caller surfaces FINISH_EVICTED backpressure instead of silently
        queueing against a cold model)."""
        for n in self.group_members(group):
            e = self.entries[n]
            if not e.resident:
                self._load(e)
        if self.budget_bytes is None:
            return True
        while self.resident_bytes() > self.budget_bytes:
            victim = self._lru_group(exclude=group)
            if victim is None:
                self.evict_group(group, on_evict)
                return False
            self.evict_group(victim, on_evict)
        return True
