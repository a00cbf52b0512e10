"""ZP-Cert: static board certification + farm control-plane race lint.

Two independent passes over the things the farm trusts implicitly:

* :mod:`repro.analysis.boardcheck` — certify a DUT engine by tracing it
  to a closed jaxpr via ABSTRACT EVAL ONLY (no device dispatch) and
  walking the equations for the hazard classes every farm bug so far
  belonged to (host callbacks in window bodies, wrong-argnum donation,
  donate-without-factory replay crashes, carry retrace drift, PRNG key
  reuse, fused scope planes over donated leaves). Rule IDs ``ZC1xx``.
* :mod:`repro.analysis.racecheck` — an AST lock-discipline lint over the
  farm control plane: ownership is declared with the lightweight
  decorators in :mod:`repro.analysis.annotations`
  (``@control_thread_only``, ``@locked("_mu")``, ...) and every
  shared-attribute mutation outside its lock or owner thread is a
  finding. Rule IDs ``RC2xx``.

``python -m repro.analysis`` runs both passes (CI's ZP-Cert gate);
``FarmManager(certify=True)`` runs boardcheck at job admission and
dead-letters uncertifiable boards with a journaled ``certify_fail``
record.
"""
import importlib

# name -> defining submodule. Resolved on first attribute access so that
# runtime modules importing ``repro.analysis.annotations`` never load the
# analysers (or the JAX internals boardcheck walks).
_EXPORTS = {
    **dict.fromkeys(("any_thread", "control_thread_only", "exclusive",
                     "locked", "slot_thread_only", "thread_confined"),
                    "annotations"),
    **dict.fromkeys(("CertReport", "Finding", "RULES", "certify_engine",
                     "certify_job", "certify_spec", "no_dispatch_guard"),
                    "boardcheck"),
    **dict.fromkeys(("RaceFinding", "check_paths", "check_source",
                     "farm_sources"), "racecheck"),
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name not in _EXPORTS:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f"{__name__}.{_EXPORTS[name]}"),
                   name)
