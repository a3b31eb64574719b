"""One rank of a 2-process DCN run: rendezvous, hybrid-mesh train, serve.

Spawned twice (rank 0 and 1) by ``tests/test_distributed.py`` and the
``dcn_multihost`` dryrun stage. Each rank owns 4 virtual CPU devices; the
two ranks rendezvous through ``jax.distributed`` exactly like two TPU
hosts would, build a hybrid (DCN x ICI) mesh with a REAL cross-process
axis — ``data`` spans the processes, ``model`` stays process-local, the
layout ``parallel/distributed.hybrid_mesh`` prescribes for pods — then:

1. serve one ``/infer`` through ``LockstepMeshServer`` (rank 0 fronts
   HTTP; the forward is one SPMD program whose collectives cross the
   process boundary),
2. run two data-parallel x tensor-parallel train steps on the same mesh
   (gradient psum over the DCN axis — the one collective per step that
   tolerates DCN latency), and
3. run ring attention with the SEQUENCE axis spanning both processes —
   the long-context story: K/V shards rotate via ppermute across the
   host boundary, checked exact against the replicated full-sequence
   forward, and
4. run Ulysses all-to-all attention over the same cross-process seq
   axis (the head-scattering SP mode), also checked exact.

The reference needs nothing to span hosts because nothing is shared —
each worker holds a whole model and the gateway re-POSTs JSON
(``/root/reference/src/gateway.cpp:99-103``); here the MODEL spans the
hosts and the only JSON is at the client edge.

Usage: python tools/dcn_child.py <rank> <coord_port> <http_port>
"""

import os
import sys


def main() -> int:
    rank, coord_port, http_port = (int(sys.argv[1]), sys.argv[2],
                                   int(sys.argv[3]))
    ndev = int(os.environ.get("DCN_CHILD_LOCAL_DEVICES", "4"))
    # Before any jax import: per-process virtual CPU devices.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={ndev}")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_engine.parallel.distributed import hybrid_mesh, initialize

    info = initialize(coordinator_address=f"127.0.0.1:{coord_port}",
                      num_processes=2, process_id=rank)
    assert info["num_processes"] == 2, info
    assert info["global_devices"] == 2 * ndev, info
    # data axis (size 2) crosses the processes = DCN; model (size ndev)
    # stays inside one process = ICI.
    mesh = hybrid_mesh((1, ndev), ("data", "model"), dcn_shape=(2, 1))
    assert dict(mesh.shape) == {"data": 2, "model": ndev}
    proc_of = {d.process_index for d in mesh.devices[0].ravel()}
    assert len(proc_of) == 1, "a data shard must live on ONE process"
    print(f"MESH-OK {rank} {dict(mesh.shape)}", flush=True)

    from tpu_engine.models.registry import (
        _ensure_builtin_models_imported,
        create_model,
    )
    from tpu_engine.training.train import make_train_step, shard_params_tp

    _ensure_builtin_models_imported()
    spec = create_model("mlp", input_dim=16, hidden_dim=4 * ndev,
                        output_dim=16, num_layers=2)
    host_params = spec.init(jax.random.PRNGKey(0))  # identical on both ranks

    def gput(arr, sharding):
        arr = np.asarray(arr)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])

    p_sh = shard_params_tp(host_params, mesh, "model")
    params = jax.tree.map(gput, host_params, p_sh)

    # -- 1. mesh serving: one /infer through the lockstep front --------------
    from tpu_engine.parallel.multihost_serving import LockstepMeshServer

    srv = LockstepMeshServer(mesh, spec.apply, params, sample_shape=(16,))
    srv.run(http_port=http_port if rank == 0 else None)
    print(f"SERVE-OK {rank}", flush=True)

    # -- 2. dp2 x tp{ndev} train steps: gradient psum crosses the DCN axis ---
    init_state, train_step = make_train_step(spec.apply, dtype=jnp.float32)
    state = jax.jit(init_state)(params)
    x_sh = NamedSharding(mesh, P("data", None))
    rng = np.random.default_rng(5)
    x = gput(rng.standard_normal((4, 16)).astype(np.float32), x_sh)
    y = gput(rng.standard_normal((4, 16)).astype(np.float32), x_sh)
    jitted = jax.jit(train_step, donate_argnums=(0,))
    state, loss1 = jitted(state, x, y)
    state, loss2 = jitted(state, x, y)
    l1, l2 = float(loss1), float(loss2)
    assert np.isfinite(l1) and np.isfinite(l2), (l1, l2)
    assert l2 < l1, f"loss must fall across DCN train steps: {l1} -> {l2}"
    print(f"TRAIN-OK {rank} {l1:.6f}->{l2:.6f}", flush=True)

    # -- 3. ring attention with the seq axis spanning BOTH processes ---------
    import functools

    from tpu_engine.models.transformer import (
        TransformerConfig,
        transformer_apply,
        transformer_init,
    )
    from tpu_engine.parallel.ring import ring_attention

    from tpu_engine.parallel.ring import ulysses_attention

    seq_mesh = hybrid_mesh((ndev,), ("seq",), dcn_shape=(2,))
    n_seq = 2 * ndev
    rep = NamedSharding(seq_mesh, P())
    toks_host = np.asarray(
        np.random.default_rng(9).integers(0, 64, (1, 4 * n_seq)), np.int32)
    toks_sp = gput(toks_host, NamedSharding(seq_mesh, P(None, "seq")))
    toks_rep = gput(toks_host, rep)

    def check_sp_mode(marker, cfg_sp, key, attn):
        """One SP arm: sharded-seq forward with `attn` must equal the
        replicated full-sequence forward."""
        p_rep = jax.tree.map(
            lambda a: gput(np.asarray(a), rep),
            transformer_init(jax.random.PRNGKey(key), cfg_sp))

        @functools.partial(jax.jit, out_shardings=rep)
        def fwd_sp(p, t):
            return transformer_apply(
                p, t, cfg_sp, dtype=jnp.float32,
                attn_fn=lambda q, k, v, causal, mask: attn(
                    q, k, v, causal=causal, kv_mask=mask))

        @functools.partial(jax.jit, out_shardings=rep)
        def fwd_ref(p, t):
            return transformer_apply(p, t, cfg_sp, dtype=jnp.float32)

        ls = np.asarray(fwd_sp(p_rep, toks_sp))
        lref = np.asarray(fwd_ref(p_rep, toks_rep))
        assert np.isfinite(ls).all(), f"non-finite {marker} logits"
        np.testing.assert_allclose(ls, lref, rtol=2e-4, atol=2e-4)
        print(f"{marker} {rank} "
              f"maxdiff={float(np.max(np.abs(ls - lref))):.2e}", flush=True)

    check_sp_mode(
        "RING-DCN-OK",
        TransformerConfig(vocab=64, n_layers=2, d_model=16, n_heads=4,
                          d_ff=32, max_seq=8 * n_seq, causal=True),
        key=1,
        attn=functools.partial(ring_attention, mesh=seq_mesh,
                               axis_name="seq"))
    # -- 4. Ulysses all-to-all over the same cross-process seq axis: the
    # head-scattering SP mode (two all_to_all collectives instead of n-1
    # ppermute hops). Needs n_heads % axis_size == 0, so its dims derive
    # from n_seq — any DCN_CHILD_LOCAL_DEVICES value stays valid.
    check_sp_mode(
        "ULYSSES-DCN-OK",
        TransformerConfig(vocab=64, n_layers=2, d_model=4 * n_seq,
                          n_heads=n_seq, d_ff=8 * n_seq,
                          max_seq=8 * n_seq, causal=True),
        key=2,
        attn=functools.partial(ulysses_attention, mesh=seq_mesh,
                               axis_name="seq"))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except OSError as exc:
        import errno

        if getattr(exc, "errno", None) == errno.EADDRINUSE:
            # The parent probed these ports with free_ports() and another
            # process bound one first. A distinct marker + exit code lets
            # the launcher (training/dryrun.run_dcn_pair) classify this as
            # a port race and relaunch on fresh ports.
            print(f"BIND-FAIL {exc}", flush=True)
            sys.exit(97)
        raise
