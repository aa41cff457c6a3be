#!/usr/bin/env python
"""Multi-host rehearsal: one process per host, real cross-process collectives.

The executable sanity check of the multi-host launch path
(docs/MULTIHOST.md; the reference's cluster recipe analog —
/root/reference/EC2.md:19-29). Each process:

  1. calls ``distributed_init`` (explicit coordinator, or auto-detect on a
     real pod slice),
  2. builds the global 1-D data mesh over every device of every host,
  3. assembles a process-local shard of a known global matrix,
  4. runs ``linalg.gram`` — the shard_map + psum allreduce under every
     exact solver — so the collective actually crosses process boundaries,
  5. checks the result against the closed form and prints
     ``REHEARSAL_OK rel_err=...``.

Fallback (CPU rehearsal only): jax's CPU backend refuses multi-process
computations ("Multiprocess computations aren't implemented on the CPU
backend"), so when the psum path raises exactly that, the cross-process
sum is rehearsed through the coordination service instead — each process
publishes its local partial Gram to the distributed KV store and reduces
everyone's partials, deadline-bounded by the reliability helpers. The
collective still crosses process boundaries (through the coordinator
rather than ICI), so the launch path, mesh, and data layout stay
exercised code on every backend. On TPU the psum path runs as-is.

Coordinator joins and KV waits use keystone_tpu.reliability
(RetryPolicy / Deadline) — the same classified-retry machinery the
executor uses — so a slow-starting peer process deflakes instead of
failing the rehearsal.

On a TPU pod slice (one process per host, auto-detected coordination):
    python scripts/multihost_rehearsal.py

As the 2-process CPU rehearsal (what tests/parallel/test_multihost.py
runs; 4 virtual devices per process → an 8-device global mesh):
    python scripts/multihost_rehearsal.py \
        --coordinator 127.0.0.1:9911 --num-hosts 2 --host-id $i \
        --virtual-devices 4
"""

from __future__ import annotations

import argparse
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (omit on a real pod: auto-detect)")
    ap.add_argument("--num-hosts", type=int, default=None)
    ap.add_argument("--host-id", type=int, default=None)
    ap.add_argument("--virtual-devices", type=int, default=0,
                    help=">0: CPU rehearsal with this many virtual devices per process")
    args = ap.parse_args()

    if args.virtual_devices:
        # Must land before any backend init.
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags +
                f" --xla_force_host_platform_device_count={args.virtual_devices}"
            ).strip()

    from keystone_tpu.parallel.mesh import distributed_init, make_mesh
    from keystone_tpu.reliability import RetryPolicy

    # Coordinator join: classified retry — a peer process that hasn't
    # bound its port yet surfaces as a transient connect/barrier error.
    RetryPolicy(max_attempts=3, base_delay_s=1.0, max_delay_s=5.0).call(
        distributed_init, args.coordinator, args.num_hosts, args.host_id,
        label="distributed_init",
    )

    import jax
    import jax.numpy as jnp  # noqa: F401  (backend init ordering)
    import numpy as np

    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel import linalg

    n_local = len(jax.local_devices())
    n_global = len(jax.devices())
    print(f"host {jax.process_index()}/{jax.process_count()}: "
          f"{n_local} local / {n_global} global devices", flush=True)
    if args.num_hosts is not None:
        assert jax.process_count() == args.num_hosts, (
            jax.process_count(), args.num_hosts)
        assert n_global == n_local * args.num_hosts, (n_global, n_local)

    mesh = make_mesh(devices=jax.devices())

    # Known global matrix, assembled shard-by-shard on whichever process
    # owns the shard (no single host ever holds the whole thing — the
    # multi-host data layout of SURVEY §2.9).
    n, d = 8 * n_global, 16
    full = np.arange(n * d, dtype=np.float32).reshape(n, d) % 23 / 23.0
    sharding = NamedSharding(mesh, P("data", None))
    x = jax.make_array_from_callback((n, d), sharding, lambda idx: full[idx])

    try:
        ata, _ = linalg.gram(x, mesh=mesh)  # shard_map + psum across processes
        got = np.asarray(ata.addressable_data(0), np.float64)
        mode = "psum"
    except Exception as e:
        if "Multiprocess computations aren't implemented" not in str(e):
            raise
        # CPU backend: rehearse the cross-process reduction through the
        # coordination service instead (see module docstring).
        got = _kv_allreduce_gram(x, d)
        mode = "kv-allreduce"

    want = full.T.astype(np.float64) @ full
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    assert rel < 1e-5, f"cross-process gram wrong: rel_err={rel:.3e}"
    print(f"REHEARSAL_OK rel_err={rel:.2e} mode={mode}", flush=True)
    return 0


def _kv_allreduce_gram(x, d: int):
    """Cross-process Gram allreduce over the distributed KV store: publish
    the local partial AᵀA, fetch and sum every process's partial. The
    fetches are deadline-bounded (reliability.Deadline) — a dead peer
    fails the rehearsal loudly instead of hanging it."""
    import base64

    import jax
    import numpy as np

    from jax._src.distributed import global_state

    from keystone_tpu.reliability import Deadline, DeadlineExceeded

    client = global_state.client
    assert client is not None, "distributed runtime not initialized"

    local = np.zeros((d, d), np.float64)
    for shard in x.addressable_shards:
        a = np.asarray(shard.data, np.float64)
        local += a.T @ a

    pid = jax.process_index()
    client.key_value_set(
        f"rehearsal/gram/{pid}", base64.b64encode(local.tobytes()).decode()
    )

    deadline = Deadline.after(120.0)
    total = np.zeros_like(local)
    for p in range(jax.process_count()):
        left_ms = int(max(deadline.remaining(), 0.001) * 1000)
        try:
            blob = client.blocking_key_value_get(f"rehearsal/gram/{p}", left_ms)
        except Exception as e:
            raise DeadlineExceeded(
                f"peer {p}'s gram partial not published in time: {e}"
            ) from None
        total += np.frombuffer(base64.b64decode(blob), np.float64).reshape(d, d)
    return total


if __name__ == "__main__":
    sys.exit(main())
