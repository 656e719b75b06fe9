"""Pipeline-parallel numerics: GPipe(+manual TP) loss/grads must equal the
single-device reference. Runs in a subprocess with 4 forced host devices so
the main test session keeps its 1-device view."""

import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.compat import AxisType, make_mesh
    from repro.models import transformer as tfm
    from repro.distributed import pipeline as pp
    from repro.distributed import pipeline_tp as pptp
    from repro.distributed import sharding as sh

    cfg = tfm.TransformerConfig('t', n_layers=3, d_model=32, n_heads=4,
                                n_kv=2, d_ff=64, vocab=128, head_dim=8,
                                remat=False, aux_loss_weight=0.0)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (4, 2, 16), 0, cfg.vocab)
    lbls = jax.random.randint(jax.random.key(2), (4, 2, 16), 0, cfg.vocab)
    ref_loss, ref_g = jax.value_and_grad(tfm.loss_fn)(
        params, toks.reshape(8, 16), lbls.reshape(8, 16), cfg)

    for shape in [(2, 2), (1, 2), (4, 1)]:
        mesh = make_mesh(shape, ('data', 'model'),
                         axis_types=(AxisType.Auto,) * 2)
        pc = pp.plan(cfg, n_stages=shape[0], n_micro=4)
        pparams = dict(params,
                       layers=pp.pad_layer_stack(params['layers'], cfg, pc))
        with sh.activate(mesh):
            loss, grads = jax.jit(
                lambda p, t, l: pptp.pipeline_tp_loss_and_grads(
                    p, t, l, cfg, pc, mesh))(pparams, toks, lbls)
        assert abs(float(loss) - float(ref_loss)) < 5e-3, (shape, float(loss))
        for k in ('wq', 'wk', 'wo', 'w1', 'w2', 'attn_norm'):
            a = np.asarray(grads['layers'][k])[:cfg.n_layers]
            b = np.asarray(ref_g['layers'][k])
            scale = max(float(np.abs(b).max()), 1e-3)
            assert float(np.abs(a - b).max()) < 0.02 * scale, (shape, k)
        for k in ('embed', 'lm_head', 'final_norm'):
            a, b = np.asarray(grads[k]), np.asarray(ref_g[k])
            scale = max(float(np.abs(b).max()), 1e-3)
            assert float(np.abs(a - b).max()) < 0.02 * scale, (shape, k)
        # identity padding layers get exactly zero grads
        pad = np.asarray(grads['layers']['wq'])[cfg.n_layers:]
        if pad.size:
            assert float(np.abs(pad).max()) == 0.0
    print('PIPELINE-OK')
""")


@pytest.mark.slow
def test_pipeline_tp_matches_reference():
    """The cotangent psums for replicated params come from shard_map's
    varying-manual-axes types (pvary-marked carries)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=900,
    )
    assert "PIPELINE-OK" in out.stdout, out.stderr[-3000:]
