"""The spec build pipeline (layer L2).

The reference extracts executable Python out of Markdown and assembles one
flat module per (fork, preset) (`setup.py:86-112`, `pysetup/md_to_spec.py`).
This build keeps the same *contract* — a flat namespace per (fork, preset)
holding every container, constant, config object and spec function, with
later forks overriding earlier definitions — but the canonical spec sources
are Python files (`models/<fork>/*.py`) executed in fork order into a shared
namespace.  That reproduces the reference's override semantics (generated
modules re-bind names; all functions late-bind through module globals) with
a ~200-line builder instead of a Markdown parser, and makes the spec sources
directly lintable/diffable.

Public API:
    build_spec(fork, preset)      -> module-like Spec object (cached)
    spec_with_config(spec, overrides) -> fresh spec copy with config edits
"""

from __future__ import annotations

import re
import types
from pathlib import Path
from typing import Any

import yaml

from .. import telemetry


class _SpecYamlLoader(yaml.SafeLoader):
    """SafeLoader that keeps 0x… scalars as strings (PyYAML would parse
    them as hex ints, destroying Version/address byte values)."""


# prepend (add_implicit_resolver appends, and the stock int resolver for
# '0' would win): 0x… must resolve to !hexstr before tag:yaml.org,2002:int
_SpecYamlLoader.yaml_implicit_resolvers = {
    k: list(v) for k, v in yaml.SafeLoader.yaml_implicit_resolvers.items()
}
_SpecYamlLoader.yaml_implicit_resolvers["0"] = (
    [("!hexstr", re.compile(r"^0x[0-9a-fA-F]+$"))]
    + _SpecYamlLoader.yaml_implicit_resolvers.get("0", [])
)
_SpecYamlLoader.add_constructor(
    "!hexstr", lambda loader, node: str(node.value))

PKG_ROOT = Path(__file__).resolve().parent.parent

# fork DAG (mirrors `pysetup/md_doc_paths.py:17-41`)
PREVIOUS_FORK_OF: dict[str, str | None] = {
    "phase0": None,
    "altair": "phase0",
    "bellatrix": "altair",
    "capella": "bellatrix",
    "deneb": "capella",
    "electra": "deneb",
    "fulu": "electra",
    # feature forks (specs/_features/)
    "eip7732": "electra",
    "eip7805": "electra",
    "eip6800": "deneb",
    "eip7441": "capella",
}

# Mainline forks only — the default phase list for tests and generators;
# feature forks build via `build_spec` but don't join @with_all_phases
# (the reference's ALL_PHASES vs ALL_PHASES+features split,
# `test/helpers/constants.py`).
ALL_FORKS = ["phase0", "altair", "bellatrix", "capella", "deneb",
             "electra", "fulu"]
FEATURE_FORKS = ["eip7732", "eip7805", "eip6800", "eip7441"]
BUILDABLE_FORKS = ALL_FORKS + FEATURE_FORKS

# source files per fork, executed in order; later forks only list their own
# delta files (ancestors' files run first)
SPEC_SOURCES: dict[str, list[str]] = {
    "phase0": ["beacon_chain.py", "fork_choice.py", "validator.py",
               "genesis.py", "p2p.py"],
    "altair": ["beacon_chain.py", "fork.py", "light_client.py",
               "validator.py", "p2p.py"],
    "bellatrix": ["beacon_chain.py", "fork.py", "fork_choice.py",
                  "validator.py", "p2p.py", "optimistic.py"],
    "capella": ["beacon_chain.py", "fork.py", "fork_choice.py",
                "validator.py", "light_client.py", "p2p.py"],
    "deneb": ["polynomial_commitments.py", "beacon_chain.py", "fork.py",
              "fork_choice.py", "light_client.py", "p2p.py",
              "validator.py"],
    "electra": ["beacon_chain.py", "fork.py", "light_client.py",
                "validator.py", "p2p.py"],
    "fulu": ["polynomial_commitments_sampling.py", "das_core.py",
             "beacon_chain.py", "fork.py", "fork_choice.py", "p2p.py",
             "validator.py"],
    "eip7732": ["beacon_chain.py", "fork.py", "validator.py", "p2p.py"],
    "eip7805": ["beacon_chain.py", "fork.py", "fork_choice.py",
                "validator.py", "p2p.py"],
    "eip6800": ["beacon_chain.py", "fork.py"],
    "eip7441": ["beacon_chain.py", "fork.py"],
}


def fork_chain(fork: str) -> list[str]:
    chain = []
    f: str | None = fork
    while f is not None:
        chain.append(f)
        f = PREVIOUS_FORK_OF[f]
    return list(reversed(chain))


def _parse_value(v: Any) -> Any:
    if isinstance(v, str):
        if v.startswith("0x"):
            return bytes.fromhex(v[2:])
        if v.isdigit():
            return int(v)
    return v


def load_preset(preset_name: str, fork: str) -> dict[str, Any]:
    """Merge preset files of the fork and all ancestors."""
    out: dict[str, Any] = {}
    for f in fork_chain(fork):
        path = PKG_ROOT / "presets" / preset_name / f"{f}.yaml"
        if path.exists():
            with open(path) as fh:
                data = yaml.load(fh, Loader=_SpecYamlLoader) or {}
            out.update({k: _parse_value(v) for k, v in data.items()})
    return out


def load_config(config_name: str) -> dict[str, Any]:
    path = PKG_ROOT / "configs" / f"{config_name}.yaml"
    with open(path) as fh:
        data = yaml.load(fh, Loader=_SpecYamlLoader) or {}
    return {k: _parse_value(v) for k, v in data.items()}


class Configuration(types.SimpleNamespace):
    """Runtime config object; spec code reads `config.NAME`."""

    def to_dict(self) -> dict[str, Any]:
        return dict(self.__dict__)


def _preamble_namespace() -> dict[str, Any]:
    """Names available to every spec source file before execution."""
    import dataclasses
    from typing import Any, Dict, List as PyList, Optional, Sequence, Set, Tuple

    from ..ops import bls
    from ..utils.hash import hash_eth2
    from ..utils.ssz import ssz_typing as tz
    from ..utils.ssz.gindex import (
        compute_merkle_proof,
        concat_generalized_indices,
        get_generalized_index,
    )
    from ..utils.ssz.ssz_impl import (
        copy, deserialize, hash_tree_root, serialize, uint_to_bytes)

    ns: dict[str, Any] = {
        # ssz types
        **{n: getattr(tz, n) for n in (
            "Bitlist", "Bitvector", "ByteList", "ByteVector", "Bytes1",
            "Bytes4", "Bytes8", "Bytes20", "Bytes31", "Bytes32", "Bytes48",
            "Bytes96", "Container", "List", "Union", "Vector", "View",
            "boolean", "byte", "uint8", "uint16", "uint32", "uint64",
            "uint128", "uint256", "bit",
        )},
        # ssz functions
        "hash_tree_root": hash_tree_root,
        "serialize": serialize,
        "ssz_serialize": serialize,
        "ssz_deserialize": deserialize,
        "uint_to_bytes": uint_to_bytes,
        "copy": copy,
        "get_generalized_index": get_generalized_index,
        "concat_generalized_indices": concat_generalized_indices,
        "compute_merkle_proof_backing": compute_merkle_proof,
        # crypto
        "bls": bls,
        "hash": hash_eth2,
        # python utilities the spec sources use
        "dataclass": dataclasses.dataclass,
        "field": dataclasses.field,
        "Dict": Dict,
        "PyList": PyList,
        "Optional": Optional,
        "Sequence": Sequence,
        "Set": Set,
        "Tuple": Tuple,
        "Any": Any,
        "ceillog2": lambda x: (int(x) - 1).bit_length(),
        "floorlog2": lambda x: int(x).bit_length() - 1,
    }
    return ns


class Spec:
    """A built (fork, preset) spec namespace; attribute access like the
    reference's generated `eth2spec.<fork>.<preset>` module.

    Attribute get/set are live views over the exec namespace, so
    monkeypatching `spec.get_eth1_data = ...` (the reference's per-test
    stub pattern, `helpers/fork_choice.py:55-115`) is seen by every spec
    function (they late-bind through the same dict)."""

    def __init__(self, fork: str, preset_name: str, ns: dict[str, Any]):
        object.__setattr__(self, "_namespace", ns)
        ns["fork"] = fork
        ns["preset_name"] = preset_name

    def __getattr__(self, name):
        try:
            return self._namespace[name]
        except KeyError:
            raise AttributeError(f"spec has no attribute {name!r}") from None

    def __setattr__(self, name, value):
        self._namespace[name] = value

    def __repr__(self):
        return f"<Spec {self._namespace['fork']}/{self._namespace['preset_name']}>"


class _LRU:
    """Small dict-backed LRU (the reference uses the `lru-dict` C ext,
    `pysetup/spec_builders/phase0.py:47-56`; this build avoids the dep)."""

    __slots__ = ("size", "data")

    def __init__(self, size: int):
        self.size = size
        self.data: dict = {}

    def __contains__(self, key):
        return key in self.data

    def __getitem__(self, key):
        v = self.data.pop(key)
        self.data[key] = v  # move to back (most recent)
        return v

    def __setitem__(self, key, value):
        if key in self.data:
            self.data.pop(key)
        elif len(self.data) >= self.size:
            self.data.pop(next(iter(self.data)))
        self.data[key] = value


def _cache_this(key_fn, value_fn, lru_size: int):
    cache = _LRU(lru_size)

    def wrapper(*args, **kw):
        key = key_fn(*args, **kw)
        if key not in cache:
            cache[key] = value_fn(*args, **kw)
        return cache[key]

    wrapper.__wrapped__ = value_fn  # monkeypatch/debug escape hatch
    return wrapper


def _install_caches(ns: dict[str, Any]) -> None:
    """Wrap the committee/shuffle/balance lookups in per-namespace LRU
    caches, mirroring the reference's generated-spec cache layer
    (`pysetup/spec_builders/phase0.py:58-104`).  Installed after all fork
    sources executed, so the wrappers capture each fork's final overrides;
    keys lean on the SSZ engine's dirty-propagation root cache making
    `.hash_tree_root()` cheap on unchanged subtrees."""
    slots_per_epoch = int(ns["SLOTS_PER_EPOCH"])
    max_committees = int(ns.get("MAX_COMMITTEES_PER_SLOT", 64))
    epoch_at = ns["compute_epoch_at_slot"]

    def wrap(name, key_fn, size):
        if name in ns:
            ns[name] = _cache_this(key_fn, ns[name], size)

    wrap("compute_shuffled_index",
         lambda index, index_count, seed: (int(index), int(index_count),
                                           bytes(seed)),
         slots_per_epoch * 3)
    wrap("get_total_active_balance",
         lambda state: (state.validators.hash_tree_root(),
                        epoch_at(state.slot)),
         10)
    wrap("get_base_reward",
         lambda state, index: (state.validators.hash_tree_root(), state.slot,
                               int(index)),
         2048)
    wrap("get_committee_count_per_slot",
         lambda state, epoch: (state.validators.hash_tree_root(), int(epoch)),
         slots_per_epoch * 3)
    wrap("get_active_validator_indices",
         lambda state, epoch: (state.validators.hash_tree_root(), int(epoch)),
         3)
    wrap("get_beacon_committee",
         lambda state, slot, index: (state.validators.hash_tree_root(),
                                     state.randao_mixes.hash_tree_root(),
                                     int(slot), int(index)),
         slots_per_epoch * max_committees * 3)
    wrap("get_matching_target_attestations",
         lambda state, epoch: (state.hash_tree_root(), int(epoch)),
         10)
    wrap("get_matching_head_attestations",
         lambda state, epoch: (state.hash_tree_root(), int(epoch)),
         10)
    wrap("get_attesting_indices",
         lambda state, attestation: (state.randao_mixes.hash_tree_root(),
                                     state.validators.hash_tree_root(),
                                     attestation.hash_tree_root()),
         slots_per_epoch * max_committees * 3)


def _exec_sources(fork: str, ns: dict[str, Any]) -> None:
    for f in fork_chain(fork):
        ns["CURRENT_FORK"] = f
        for fname in SPEC_SOURCES.get(f, []):
            path = PKG_ROOT / "models" / f / fname
            if not path.exists():
                continue
            # dont_inherit: without it compile() inherits this module's
            # `from __future__ import annotations`, turning the spec
            # sources' container field annotations into strings (PEP 236)
            code = compile(path.read_text(), str(path), "exec",
                           dont_inherit=True)
            exec(code, ns)  # noqa: S102 - the spec sources are first-party


_SPEC_CACHE: dict[tuple[str, str], Spec] = {}


def build_spec(fork: str, preset_name: str) -> Spec:
    """Assemble (and cache) the flat executable spec for fork × preset."""
    key = (fork, preset_name)
    if key in _SPEC_CACHE:
        return _SPEC_CACHE[key]
    with telemetry.span("spec.build", fork=fork, preset=preset_name):
        ns = _preamble_namespace()
        ns.update(load_preset(preset_name, fork))
        ns["config"] = Configuration(**load_config(preset_name))
        ns["TRUSTED_SETUPS_DIR"] = str(
            PKG_ROOT / "presets" / preset_name / "trusted_setups")
        _exec_sources(fork, ns)
        _install_caches(ns)
        # bind functions' globals: they already close over `ns` via exec
        # globals
        spec = Spec(fork, preset_name, ns)
        ns["spec"] = spec
    _SPEC_CACHE[key] = spec
    return spec


def get_copy_of_spec(spec: Spec) -> Spec:
    """Fresh, uncached spec namespace for tests that monkeypatch spec
    functions (`spec.retrieve_blobs_and_proofs = stub` …): writes to the
    copy never leak into the shared `build_spec` cache.  Mirrors the
    reference's re-import isolation (`test/context.py:663-734`)."""
    with telemetry.span("spec.build", fork=spec.fork,
                        preset=spec.preset_name, copy=True):
        ns = _preamble_namespace()
        ns.update(load_preset(spec.preset_name, spec.fork))
        # carry the source spec's live config (it may hold overrides from
        # spec_with_config), not a fresh load of the preset defaults
        ns["config"] = Configuration(**spec.config.to_dict())
        ns["TRUSTED_SETUPS_DIR"] = str(
            PKG_ROOT / "presets" / spec.preset_name / "trusted_setups")
        _exec_sources(spec.fork, ns)
        _install_caches(ns)
        fresh = Spec(spec.fork, spec.preset_name, ns)
        ns["spec"] = fresh
    return fresh


_OVERRIDE_SPEC_CACHE: dict[tuple, Spec] = {}


def spec_with_config(spec: Spec, overrides: dict[str, Any]) -> Spec:
    """Fresh spec instance with config overrides (the reference's
    `with_config_overrides` re-import, `test/context.py:663-734`).
    Cached per (fork, preset, overrides) — rebuilding the namespace means
    re-executing every spec source file."""
    def _hashable(v):
        if isinstance(v, bytes):
            return bytes(v)
        if isinstance(v, (list, tuple)):
            return tuple(_hashable(x) for x in v)
        if isinstance(v, dict):
            return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
        return v

    fp = tuple(sorted((k, _hashable(v)) for k, v in overrides.items()))
    key = (spec.fork, spec.preset_name, fp)
    if key in _OVERRIDE_SPEC_CACHE:
        return _OVERRIDE_SPEC_CACHE[key]
    with telemetry.span("spec.build", fork=spec.fork,
                        preset=spec.preset_name, overrides=True):
        ns = _preamble_namespace()
        ns.update(load_preset(spec.preset_name, spec.fork))
        cfg = load_config(spec.preset_name)
        cfg.update(overrides)
        ns["config"] = Configuration(
            **{k: _parse_value(v) for k, v in cfg.items()})
        ns["TRUSTED_SETUPS_DIR"] = str(
            PKG_ROOT / "presets" / spec.preset_name / "trusted_setups")
        _exec_sources(spec.fork, ns)
        _install_caches(ns)
        fresh = Spec(spec.fork, spec.preset_name, ns)
        ns["spec"] = fresh
    _OVERRIDE_SPEC_CACHE[key] = fresh
    return fresh
