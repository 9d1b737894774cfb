"""Every `pl.pallas_call` of paddle_tpu/kernels/ carries a constant
name of the form <family>_<pass>[_<variant>]: it becomes the name of the
custom call's HLO instruction, which is how a device trace (and the
benchmark's `breakdown.device_ops`) tells one Mosaic kernel from another.
Without it the instruction is named after whatever jax transform wrapped
the call (`step_fn`, `jvp`, `transpose_jvp`)."""

import ast
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _mod(name):
    # by module path: the package re-exports functions under the same
    # names (kernels.flash_attention is the function)
    return importlib.import_module("paddle_tpu.kernels." + name)


(fa, fused_optim, latent, layer_norm, lora, mamba2_state, moe_ffn,
 quant_matmul, rpa, softmax_xent) = map(
    _mod, ("flash_attention", "fused_optim", "latent_attention",
           "layer_norm", "lora", "mamba2_state", "moe_ffn", "quant_matmul",
           "ragged_paged_attention", "softmax_xent"))

KERNELS = os.path.dirname(os.path.abspath(fa.__file__))
F32 = jnp.float32


def _z(*shape, dtype=F32):
    return jnp.zeros(shape, dtype)


def _flash_args(S=128, bias=None):
    q = _z(1, 2, S, 64)
    return q, q, q, None, bias


def _flash_bwd_panel(bias):
    q, k, v, mask, bias = _flash_args(bias=bias)
    lse = _z(1, 2, 128, fa.LANES)
    return fa._flash_bwd_pallas(q, k, v, mask, bias, q, lse, q, 0.125,
                                False, True)


def _flash_bwd_stream():
    q, k, v, mask, _ = _flash_args(S=256)
    lse = _z(1, 2, 256, fa.LANES)
    return fa._flash_bwd_stream(q, k, v, mask, q, lse, q, 0.125, False, True)


def _ragged():
    q = _z(2, 4, 2, 8)
    pages = _z(2, 6, 4, 8)
    i32 = jnp.int32
    return rpa._ragged_pallas(q, pages, pages, _z(2, dtype=i32),
                              jnp.ones(2, i32), _z(2, 3, dtype=i32),
                              0.35, None, None, True)


def _ragged_window():
    q = _z(2, 4, 2, 8)
    pages = _z(2, 6, 4, 8)
    i32 = jnp.int32
    return rpa._ragged_pallas(q, pages, pages, _z(2, dtype=i32),
                              jnp.ones(2, i32), _z(2, 3, dtype=i32),
                              0.35, None, None, True, window=4,
                              sink=_z(2), name="ragged_paged_attention_window")


def _quant():
    qw, sc = quant_matmul.quantize_weight(np.ones((256, 128), "float32"),
                                          "int8")
    return quant_matmul._quant_matmul_pallas(
        _z(16, 256), qw, sc, "int8", quant_matmul.DEFAULT_BLOCK, True)


def _lora():
    return lora._lora_delta_pallas(_z(16, 32), _z(2, 32, 8), _z(2, 8, 128),
                                   jnp.ones(2), _z(16, dtype=jnp.int32), True)


def _state_step():
    return mamba2_state._state_step_pallas(
        _z(2, 8, 16, 128), _z(2, 1, 8, 128), _z(2, 1, 8, 128),
        _z(2, 8, 128), _z(2, 8), True)


def _moe_ffn():
    i32 = jnp.int32
    return moe_ffn._grouped_ffn_pallas(
        _z(16, 128), _z(2, 128, 256), _z(2, 128, 128), _z(16, dtype=i32),
        _z(16), jnp.ones(2, i32), True)


def _latent():
    i32 = jnp.int32
    return latent._latent_pallas(
        _z(2, 4, 8, 128), _z(2, 4, 8, 64), _z(1, 4, 192, 128),
        _z(2, dtype=i32), jnp.ones(2, i32), _z(2, 2, dtype=i32), 0.1, True)


def _adam():
    p = _z(8, 128)
    return fused_optim.fused_adam_update(p, p, p, p, 1e-3, 0.9, 0.999)


def _momentum():
    p = _z(8, 128)
    return fused_optim.fused_momentum_update(p, p, p, 1e-3)


def _ln_bwd():
    x = _z(8, 128)
    return layer_norm._vjp_bwd(1e-5, (x, _z(128), _z(8), _z(8)), x)


def _xent_bwd():
    labels = _z(8, dtype=jnp.int32)
    return softmax_xent._vjp_bwd((_z(8, 128), labels, _z(8)), _z(8))


# one entry per name a device trace can show: (module file, function that
# reaches the call site, the names its pallas_call equations must carry)
SITES = [
    ("flash_attention.py", lambda: fa._flash_fwd_pallas(
        *_flash_args(), 0.125, False, True), ["flash_attention_fwd_panel"]),
    ("flash_attention.py", lambda: fa._flash_fwd_stream(
        *_flash_args(S=256)[:4], 0.125, False, True),
     ["flash_attention_fwd"]),
    ("flash_attention.py", _flash_bwd_stream,
     ["flash_attention_bwd_dq", "flash_attention_bwd_dkv"]),
    ("flash_attention.py", lambda: _flash_bwd_panel(None),
     ["flash_attention_bwd_dq_panel", "flash_attention_bwd_dkv_panel"]),
    ("flash_attention.py", lambda: _flash_bwd_panel(_z(1, 2, 128, 128)),
     ["flash_attention_bwd_dq_panel_bias", "flash_attention_bwd_dkv_panel"]),
    ("layer_norm.py", lambda: layer_norm._fwd_impl(
        _z(8, 128), _z(128), _z(128), 1e-5), ["layer_norm_fwd"]),
    ("layer_norm.py", _ln_bwd, ["layer_norm_bwd"]),
    ("softmax_xent.py", lambda: softmax_xent._fwd_impl(
        _z(8, 128), _z(8, dtype=jnp.int32)), ["softmax_xent_fwd"]),
    ("softmax_xent.py", _xent_bwd, ["softmax_xent_bwd"]),
    ("fused_optim.py", _adam, ["fused_adam"]),
    ("fused_optim.py", _momentum, ["fused_momentum"]),
    ("ragged_paged_attention.py", _ragged, ["ragged_paged_attention"]),
    # a window layer's call site carries a name of its own (the op's
    # `kernel_name` attribute), so that a trace tells it from the full
    # layers' calls
    ("ragged_paged_attention.py", _ragged_window,
     ["ragged_paged_attention_window"]),
    ("quant_matmul.py", _quant, ["quant_matmul"]),
    ("lora.py", _lora, ["lora_delta"]),
    ("mamba2_state.py", _state_step, ["mamba2_state_step"]),
    ("moe_ffn.py", _moe_ffn, ["moe_grouped_ffn"]),
    ("latent_attention.py", _latent, ["latent_paged_attention"]),
]


def _pallas_eqns(jaxpr, out):
    """Every pallas_call equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue        # the kernel's own body holds no call site
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_eqns(inner, out)
    return out


@pytest.mark.parametrize("site", SITES,
                         ids=[",".join(s[2]) for s in SITES])
def test_pallas_call_equation_carries_its_constant_name(site, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    _file, fn, want = site
    eqns = _pallas_eqns(jax.make_jaxpr(fn)().jaxpr, [])
    assert [e.params["name"] for e in eqns] == want


def _call_sites():
    """(file, line, the `name=` keyword's node or None) of every
    `pallas_call(...)` under paddle_tpu/kernels/, from the source."""
    found = []
    for f in sorted(os.listdir(KERNELS)):
        if not f.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(KERNELS, f)).read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", None) == "pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                found.append((f, node.lineno, kw.get("name")))
    return found


def test_every_call_site_is_named_and_no_two_share_a_name():
    sites = _call_sites()
    assert len(sites) >= 16
    names = []
    for f, line, node in sites:
        assert node is not None, f"{f}:{line}: pallas_call without name="
        if isinstance(node, ast.Constant):
            names.append(node.value)
        else:
            # one shared driver: the name is its caller's (fused_optim;
            # the ragged kernel's by kind of attention layer)
            assert (f, ast.unparse(node)) in (
                ("fused_optim.py", "name"),
                ("ragged_paged_attention.py", "name"))
    assert len(set(names)) == len(names), names
    # every name the source gives is one the traced sites above carry,
    # and every file with a call site is traced
    traced = {n for _f, _fn, want in SITES for n in want}
    assert set(names) <= traced
    assert {f for f, _l, _n in sites} == {s[0] for s in SITES}
    for n in traced:
        assert n == n.lower() and "[" not in n and " " not in n


def test_names_stay_whole_under_the_executors_grad_ops(monkeypatch):
    """A grad op re-traces its forward under jax.vjp, and jax wraps the
    innermost scope of what it transforms: `transpose(jvp(<scope>))`.
    The grad lowering opens a scope of the op's type to take that wrapper,
    so the kernel's name is the last component whole; the custom call's
    instruction is named by that component (PERF.md, PR 26)."""
    import paddle_tpu as fluid

    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data("x", [128])
        y = fluid.layers.layer_norm(fluid.layers.fc(x, 128))
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(0.1).minimize(loss)
    scope = fluid.Scope()
    feed = {"x": np.ones((8, 128), "float32")}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        bound = exe.bind(main, feed, [loss], scope=scope)
        bound._resolve_state()
        jaxpr = jax.make_jaxpr(bound.compiled.fn)(
            bound.base_key, np.int32(0), *bound.feed_avals,
            *bound.state_vals)
    eqns = _pallas_eqns(jaxpr.jaxpr, [])
    last = [str(e.source_info.name_stack).split("/")[-1] for e in eqns]
    assert sorted(set(last)) == ["layer_norm_bwd", "layer_norm_fwd"]
    assert last == [e.params["name"] for e in eqns]
    assert any("transpose(jvp(layer_norm))" in str(e.source_info.name_stack)
               for e in eqns)
