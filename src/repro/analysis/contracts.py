"""Layer 1 of the static-analysis subsystem: declarative IR contracts.

A ``Contract`` is one compiled-HLO invariant, stated once, checked against
every configuration of the round matrix (``repro.analysis.ir`` builds the
``RoundArtifact`` per configuration). The five contracts encode the repo's
hardest-won guarantees:

* ``client-scope-clean`` — zero collectives inside the per-client
  local-train + encode region (the ``CLIENT_SCOPE`` named scope); on the
  vmap fan-out (no mesh) the whole module must be collective-free.
* ``fused-gather-bounded`` — the fused 3SFC decode's all_gather carries
  only the tiny ``(D_syn, s)`` payloads: total gather bytes bounded by
  ``FUSED_GATHER_FACTOR × local payload bytes + FUSED_GATHER_SLACK``.
  This is THE definition ``benchmarks/bench_collectives.py`` gates with.
* ``no-host-callbacks`` — no ``pure_callback`` / ``io_callback`` /
  ``debug.print`` lowered into a jitted round body (they all become
  ``*callback*`` custom-calls in the optimized HLO).
* ``ef-donation-aliased`` — the donated ``FLState`` EF buffers are
  actually input→output aliased in the compiled executable
  (``input_output_alias`` in the module header), so the N×d residual
  never doubles in memory.
* ``wire-dtype-policy`` — in codec mode what crosses the boundary is the
  framed ``uint8`` stream (u8 all_gather operands sized in whole frames);
  float-typed gather traffic is metrics-only (≤ the metadata slack), and
  the codec's declared dtype policy is a registered one.

``encode_region_collectives`` / ``collective_summary`` are the shared
extraction API — benches and tests go through them instead of re-deriving
scope filters from ``utils.hlo_analyzer`` (one definition per rule).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro.fl.round import CLIENT_SCOPE
from repro.utils import hlo_analyzer as H

# fused-decode gather bound: total gathered bytes per device must stay
# within FACTOR × the local clients' payload bytes plus SLACK for the
# (N,)-shaped metrics gathers — the O(N·payload) claim, as a constant
FUSED_GATHER_FACTOR = 2.0
FUSED_GATHER_SLACK_BYTES = 1024.0

# codec mode: non-u8 gather traffic (losses/cosines/payload-float metrics)
# allowed before it counts as a float tree leaking onto the wire
WIRE_METADATA_SLACK_BYTES = 1024.0

# custom-call targets that mean "host round-trip inside the jitted body":
# jax lowers pure_callback / io_callback / debug.print / debug.callback to
# per-backend python-callback custom-calls
_CALLBACK_TARGET_RE = re.compile(
    r'custom_call_target="([^"]*callback[^"]*)"')


# ---------------------------------------------------------------------------
# HLO extraction helpers (the API benches/tests consume)
# ---------------------------------------------------------------------------


def encode_region_collectives(hlo_text: str) -> List[H.CollectiveInstr]:
    """Collectives inside the per-client encode region — the single
    definition of the CLIENT_SCOPE rule's extraction."""
    return H.collectives_in_scope(hlo_text, CLIENT_SCOPE)


def collective_summary(hlo_text: str) -> Dict[str, Any]:
    """Per-module collective bill + encode-region census, the record shape
    ``experiments/results/collectives.json`` carries per compiled round."""
    cols = H.collectives(hlo_text)
    by_kind: Dict[str, float] = {}
    for c in cols:
        by_kind[c.kind] = by_kind.get(c.kind, 0.0) + c.total_bytes
    scoped = encode_region_collectives(hlo_text)
    return {
        "collective_bytes_per_round": sum(c.total_bytes for c in cols),
        "collective_count": len(cols),
        "bytes_by_kind": by_kind,
        "encode_region_collectives": len(scoped),
        "encode_region_ops": [c.kind for c in scoped],
    }


def host_callbacks(hlo_text: str) -> List[str]:
    """Custom-call targets in the module that are host python callbacks."""
    return _CALLBACK_TARGET_RE.findall(hlo_text)


def aliased_param_indices(hlo_text: str) -> frozenset:
    """Parameter numbers input→output aliased in the module header.

    The header carries ``input_output_alias={ {out}: (param, {sub}, kind),
    ... }``; the donation contract only needs the set of aliased parameter
    positions, read from the second element of each pair.
    """
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return frozenset()
    i = start + len("input_output_alias=")
    depth = 0
    end = i
    for j in range(i, min(len(hlo_text), i + 1_000_000)):
        ch = hlo_text[j]
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    block = hlo_text[i:end + 1]
    return frozenset(int(m) for m in re.findall(r"\(\s*(\d+)\s*,", block))


# ---------------------------------------------------------------------------
# the artifact + the rule engine
# ---------------------------------------------------------------------------


@dataclass
class RoundArtifact:
    """One compiled round configuration, everything a Contract may probe.

    ``config`` is the matrix point (kind/fanout/wire/fused/faulted);
    ``hlo_text`` the optimized per-device module. The remaining fields are
    config-derived expectations: the entry-parameter positions of the EF
    leaves (donation), the local payload byte budget (fused gather bound)
    and the codec's declared layout (wire dtype).
    """

    config: Dict[str, Any]
    hlo_text: str
    ef_param_indices: Tuple[int, ...] = ()
    payload_bytes_local: Optional[float] = None
    codec_nbytes: Optional[int] = None
    codec_policy: Optional[str] = None
    num_clients: int = 0
    client_shards: int = 1

    @property
    def label(self) -> str:
        c = self.config
        return (f"{c.get('kind')}/{c.get('fanout')}/{c.get('wire')}"
                + ("/fused" if c.get("fused") else "")
                + ("/faulted" if c.get("faulted") else ""))


@dataclass(frozen=True)
class Contract:
    """One declarative IR rule: ``applies`` scopes it to the matrix points
    it is meaningful for, ``check`` returns violation messages (empty =
    clean). Adding a rule for a new strategy is appending one of these to
    ``CONTRACTS`` (see README §Static analysis)."""

    name: str
    description: str
    applies: Callable[[RoundArtifact], bool]
    check: Callable[[RoundArtifact], List[str]]


def _check_client_scope(a: RoundArtifact) -> List[str]:
    if a.config.get("fanout") == "shard_map":
        scoped = encode_region_collectives(a.hlo_text)
        return [f"{a.label}: {c.kind} ({c.total_bytes:.0f} B) inside "
                f"{CLIENT_SCOPE} (op_name={c.op_name!r})" for c in scoped]
    # vmap fan-out compiles mesh-free: the whole module is collective-free
    cols = H.collectives(a.hlo_text)
    return [f"{a.label}: {c.kind} ({c.total_bytes:.0f} B) in a mesh-free "
            f"vmap round" for c in cols]


def _check_fused_gather(a: RoundArtifact) -> List[str]:
    assert a.payload_bytes_local is not None, \
        f"{a.label}: fused artifact missing payload_bytes_local"
    gathered = sum(c.total_bytes for c in H.collectives(a.hlo_text)
                   if c.kind == "all-gather")
    bound = (FUSED_GATHER_FACTOR * a.payload_bytes_local
             + FUSED_GATHER_SLACK_BYTES)
    if gathered > bound:
        return [f"{a.label}: fused gather moves {gathered:.0f} B > bound "
                f"{bound:.0f} B ({FUSED_GATHER_FACTOR}x local payload "
                f"{a.payload_bytes_local:.0f} B + "
                f"{FUSED_GATHER_SLACK_BYTES:.0f} B slack)"]
    return []


def _check_host_callbacks(a: RoundArtifact) -> List[str]:
    return [f"{a.label}: host callback custom-call {t!r} in the jitted "
            f"round body" for t in host_callbacks(a.hlo_text)]


def _check_ef_donation(a: RoundArtifact) -> List[str]:
    aliased = aliased_param_indices(a.hlo_text)
    missing = [i for i in a.ef_param_indices if i not in aliased]
    if missing:
        return [f"{a.label}: EF leaf parameter(s) {missing} not "
                f"input->output aliased (donated buffers not reused; "
                f"aliased set: {sorted(aliased)})"]
    return []


def _check_wire_dtype(a: RoundArtifact) -> List[str]:
    from repro.comm.frame import HEADER_BYTES, POLICY_IDS
    probs: List[str] = []
    if a.codec_policy not in POLICY_IDS:
        probs.append(f"{a.label}: codec declares unregistered dtype policy "
                     f"{a.codec_policy!r} (registered: {sorted(POLICY_IDS)})")
    if a.codec_nbytes is None or a.codec_nbytes <= HEADER_BYTES:
        probs.append(f"{a.label}: codec frame size {a.codec_nbytes} must "
                     f"exceed the {HEADER_BYTES} B header")
        return probs
    if a.config.get("fanout") != "shard_map":
        return probs        # no boundary collective to inspect mesh-free
    gathers = [c for c in H.collectives(a.hlo_text)
               if c.kind == "all-gather"]
    u8 = sum(b for c in gathers for dt, b in c.operands if dt == "u8")
    other = sum(b for c in gathers for dt, b in c.operands if dt != "u8")
    local = a.num_clients // max(a.client_shards, 1)
    want = float(a.codec_nbytes * local)
    if u8 < want:
        probs.append(f"{a.label}: u8 gather carries {u8:.0f} B, expected at "
                     f"least {want:.0f} B ({local} local frames x "
                     f"{a.codec_nbytes} B)")
    elif u8 % a.codec_nbytes:
        probs.append(f"{a.label}: u8 gather bytes {u8:.0f} are not whole "
                     f"{a.codec_nbytes} B frames")
    if other > WIRE_METADATA_SLACK_BYTES:
        probs.append(f"{a.label}: {other:.0f} B of non-u8 gather traffic in "
                     f"codec mode (> {WIRE_METADATA_SLACK_BYTES:.0f} B "
                     f"metrics slack) — a float tree is crossing the wire")
    return probs


CONTRACTS: Tuple[Contract, ...] = (
    Contract(
        "client-scope-clean",
        "zero collectives inside the per-client encode region "
        f"({CLIENT_SCOPE}); mesh-free vmap rounds are collective-free",
        lambda a: True,
        _check_client_scope),
    Contract(
        "fused-gather-bounded",
        "fused-decode all_gather bytes bounded by "
        f"{FUSED_GATHER_FACTOR}x local payload + "
        f"{FUSED_GATHER_SLACK_BYTES:.0f} B",
        lambda a: bool(a.config.get("fused"))
        and a.config.get("fanout") == "shard_map",
        _check_fused_gather),
    Contract(
        "no-host-callbacks",
        "no pure_callback/io_callback/debug.print custom-calls in the "
        "compiled round",
        lambda a: True,
        _check_host_callbacks),
    Contract(
        "ef-donation-aliased",
        "donated FLState EF buffers are input->output aliased in the "
        "executable",
        lambda a: True,
        _check_ef_donation),
    Contract(
        "wire-dtype-policy",
        "codec-mode boundary traffic is whole u8 frames under a registered "
        "dtype policy; float gathers are metrics-sized",
        lambda a: a.config.get("wire") == "codec",
        _check_wire_dtype),
)


def run_contracts(artifacts: List[RoundArtifact],
                  contracts: Tuple[Contract, ...] = CONTRACTS,
                  ) -> Dict[str, Any]:
    """Evaluate every contract against every artifact it applies to.

    Returns the static record's IR stanza: per-contract evaluation
    counts + violation messages, the covered config labels, and totals.
    """
    per: Dict[str, Dict[str, Any]] = {}
    total_eval = 0
    total_viol = 0
    for c in contracts:
        evaluated = 0
        violations: List[str] = []
        for a in artifacts:
            if not c.applies(a):
                continue
            evaluated += 1
            violations.extend(c.check(a))
        per[c.name] = {"description": c.description,
                       "evaluated": evaluated,
                       "violations": violations}
        total_eval += evaluated
        total_viol += len(violations)
    return {
        "configs": [a.label for a in artifacts],
        "configs_evaluated": len(artifacts),
        "contracts": per,
        "rules_evaluated": total_eval,
        "violations": total_viol,
    }
