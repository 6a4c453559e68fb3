"""Compile-time contract checking: IR invariants, repo lints, protocol.

Three layers, one driver (``scripts/check_static.py`` →
``experiments/results/static.json``):

* ``contracts``/``ir`` — declarative ``Contract`` rules over the compiled
  HLO of every constructible ``build_fl_round`` configuration;
* ``lint`` — repo-specific AST rules over ``src/``;
* ``protocol`` — the ``MSG_*`` transition table + a race-detector-lite
  for the socket server's shared state.

Benches and tests import the extraction API from here
(``collective_summary``, ``encode_region_collectives``) so each invariant
has exactly one definition.
"""
from repro.analysis.contracts import (CLIENT_SCOPE, CONTRACTS, Contract,
                                      RoundArtifact, aliased_param_indices,
                                      collective_summary,
                                      encode_region_collectives,
                                      host_callbacks, run_contracts)

__all__ = [
    "CLIENT_SCOPE",
    "CONTRACTS",
    "Contract",
    "RoundArtifact",
    "aliased_param_indices",
    "collective_summary",
    "encode_region_collectives",
    "host_callbacks",
    "run_contracts",
]
