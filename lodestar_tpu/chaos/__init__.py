"""Chaos engineering: deterministic fault injection + the campaign that
proves every induced failure is diagnosable AND self-healing
(docs/chaos.md; ROADMAP item 5's fault-injection half).

Public surface:

- ``CHAOS``            process-wide controller; ``CHAOS.armed`` is the
                       constant-time disarmed gate every seam reads
- ``FaultPlan`` / ``FaultSpec``   the seeded, deterministic plan
- ``install_from_env`` spawn-child activation (``LODESTAR_TPU_CHAOS_PLAN``)
- ``corrupt_file``     deterministic byte-flipper for cache-corruption runs
- ``DeviceLostError`` / ``InjectedCompileError`` / ``InjectedIOError`` /
  ``FaultInjected``    the typed injected failures

``tools/chaos_campaign.py`` drives the full campaign and reports
``time_to_quarantine_s`` / ``time_to_recover_s`` / ``verdicts_lost``.
"""

from .plan import (  # noqa: F401
    CHAOS,
    KNOWN_SEAMS,
    PLAN_ENV,
    ChaosController,
    DeviceLostError,
    FaultInjected,
    FaultPlan,
    FaultSpec,
    InjectedCompileError,
    InjectedIOError,
    corrupt_file,
    install_from_env,
)
