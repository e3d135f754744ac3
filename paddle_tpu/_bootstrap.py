"""Multi-controller rendezvous helper (import-light: safe to call before
any backend use). One copy of the launcher env protocol, shared by the
package-import bootstrap and ``distributed.init_parallel_env``."""
from __future__ import annotations

import os

# set by paddle.distributed.launch for its OWN workers; the public
# PADDLE_* vars alone must not trigger a rendezvous in arbitrary
# subprocesses that merely inherit them (they would join as a duplicate
# process_id and hang)
LAUNCHER_MARKER = "PADDLE_TPU_LAUNCHED"


def rendezvous_from_env():
    """jax.distributed.initialize from the PADDLE_* env protocol.

    Returns True if a rendezvous was performed. No-op when the env does
    not describe a multi-process job or the coordination client already
    exists."""
    n = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if n <= 1:
        return False
    import jax
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        return False
    coordinator = os.environ.get("PADDLE_MASTER") or os.environ.get(
        "MASTER_ADDR", "127.0.0.1:8701"
    )
    # consume the marker BEFORE initializing: grandchild processes that
    # inherit the env must not try to join as duplicate process_ids
    os.environ.pop(LAUNCHER_MARKER, None)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=n,
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", "0")),
        )
    except RuntimeError as e:
        if "must be called before" in str(e):
            raise RuntimeError(
                "multi-process rendezvous requires the PADDLE_* env to be "
                "set BEFORE the first XLA backend use. Use "
                "paddle.distributed.launch, or export the env first."
            ) from e
        raise
    return True
