"""zamba2-7b at a tiny size on the CPU (12 layers, shared blocks 0 and 1
before layers 6 and 11, D 64, 4 heads of 32 over the 128-wide concat, 2
groups, adapter rank 8): the program against the plain float32 reference of
``benchmarks/chip/families/zamba2.py``, planted faults against the same
tolerances, the reference against HF transformers' ``Zamba2ForCausalLM``,
and the parameter counts at the published and the benchmark's sizes."""
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
for _p in (str(CHIP / "tests"), str(CHIP)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import zamba2_tiny  # noqa: E402
from chipbench import spec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.models import build_model, hybrid, mamba2  # noqa: E402

fam = spec.family("zamba2")
KEY = jax.random.PRNGKey(4)
P, T = 16, 24          # prompt, then decode to T - 1
#: program against reference, float32 throughout: the two differ only in the
#: order of float32 operations (chunked scan against the token-by-token
#: recurrence, the q-block scan against whole-row softmax), read 6.6e-5 on
#: logits of magnitude 4.6; 5e-4 leaves 7x of room, and the least planted
#: fault (the scale 1/sqrt(hd)) moves a logit by 0.39
LOGIT_TOL = 5e-4
#: gradients' relative error per leaf: read 1.1e-6 at most, for the same reason
GRAD_TOL = 1e-4


def _setup():
    c = zamba2_tiny.config()
    w = jax.tree.map(lambda a: a.astype(jnp.float32), fam.make_weights(KEY, c))
    toks = np.random.default_rng(1).integers(0, 256, (2, T), dtype=np.int32)
    return c, w, toks


def _served_logits(cfg, params, toks):
    """Prefill P tokens, then decode through the cache: the logits at
    positions P - 1 .. T - 2, as the serving path makes them."""
    model = build_model(cfg)
    cache = jax.tree.map(lambda a: a.astype(jnp.float32),
                         model.init_cache(ShapeConfig("s", 32, 2, "decode"), 2))
    with jax.default_matmul_precision("highest"):
        lg, cache = jax.jit(model.prefill)(params, {"tokens": jnp.asarray(toks[:, :P])},
                                           cache)
        out = [lg[:, -1]]
        dec = jax.jit(model.decode)
        for t in range(P, T - 1):
            lg, cache = dec(params, cache, jnp.asarray(toks[:, t]), jnp.int32(t))
            out.append(lg[:, -1])
    return np.stack(out, axis=1)


@pytest.fixture(scope="module")
def reference():
    c, w, toks = _setup()
    # the reference rebuilds the same (bf16-stored) weights from the key
    ref = fam.logits(KEY, c, jnp.asarray(toks), np.arange(P - 1, T - 1))
    return c, w, toks, np.asarray(ref)


def test_prefill_and_decode_match_the_reference(reference):
    c, w, toks, ref = reference
    got = _served_logits(fam.program_config(c), fam.to_program(w), toks)
    np.testing.assert_allclose(got, ref, rtol=0, atol=LOGIT_TOL)


def test_loss_and_gradients_match_the_reference(reference):
    c, w, toks, _ = reference
    model = build_model(fam.program_config(c))
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(model.loss)(fam.to_program(w),
                                                {"tokens": jnp.asarray(toks)})
    lr, gr = jax.value_and_grad(fam.loss)(w, jnp.asarray(toks), c)
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    rel = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                       gp, fam.to_program(gr))
    assert max(jax.tree.leaves(rel)) < GRAD_TOL, rel


def _one_group(cfg, params):
    """The same weights read as one group: every head reads group 0's B and C
    (group 1's columns dropped), and the gated norm spans all channels."""
    Di, GN, N = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state, cfg.ssm_state
    keep = np.r_[0:Di, Di:Di + N, Di + GN:Di + GN + N]        # x, B0, C0 of xBC
    proj = np.r_[0:Di, Di + keep, 2 * Di + 2 * GN:params["mamba"]["in_proj"].shape[-1]]
    m = dict(params["mamba"], in_proj=params["mamba"]["in_proj"][..., proj],
             conv_w=params["mamba"]["conv_w"][..., keep],
             conv_b=params["mamba"]["conv_b"][..., keep])
    return dataclasses.replace(cfg, ssm_ngroups=1), dict(params, mamba=m)


def _fault(name, cfg, params, monkeypatch):
    if name == "adapter_left_out":
        ad = params["adapters"]
        return cfg, dict(params, adapters=dict(ad, a=jnp.zeros_like(ad["a"])))
    if name == "one_block_for_both":
        b = jax.tree.map(lambda a: jnp.broadcast_to(a[:1], a.shape), params["blocks"])
        return cfg, dict(params, blocks=b)
    if name == "scale_one_over_sqrt_hd":
        monkeypatch.setattr(hybrid, "attn_scale", lambda cfg: cfg.head_dim ** -0.5)
    if name == "one_group":
        return _one_group(cfg, params)
    if name == "t_on_the_residual":
        run = hybrid._mamba_run
        monkeypatch.setattr(hybrid, "_mamba_run", lambda x, t, *a: run(
            x if t is None else x + t, None, *a))
    if name == "kv_not_written":
        block = hybrid.shared_block

        def stale(x, x0, bp, ap, cfg, shd, positions, kv=None, pos=None):
            t, new = block(x, x0, bp, ap, cfg, shd, positions, kv, pos)
            return t, (kv if pos is not None else new)
        monkeypatch.setattr(hybrid, "shared_block", stale)
    return cfg, params


@pytest.mark.parametrize("name", ["adapter_left_out", "one_block_for_both",
                                  "scale_one_over_sqrt_hd", "one_group",
                                  "t_on_the_residual", "kv_not_written"])
def test_planted_fault_fails_the_tolerance(reference, name, monkeypatch):
    c, w, toks, ref = reference
    cfg, params = _fault(name, fam.program_config(c), fam.to_program(w), monkeypatch)
    err = np.abs(_served_logits(cfg, params, toks) - ref).max()
    assert err > 100 * LOGIT_TOL, (name, err)


@pytest.mark.parametrize("groups", [1, 2])
def test_mamba_block_groups_against_the_recurrence(groups):
    """One Mamba2 block, chunked prefill and step-by-step decode, against the
    sequential recurrence with each head reading its group's B and C and the
    gated norm taken over each group's channels apart."""
    cfg = dataclasses.replace(get_config("zamba2-7b").reduced(), ssm_ngroups=groups,
                              ssm_dt_min=0.0)
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                      build_model(cfg).init(jax.random.PRNGKey(2))["mamba"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model), jnp.float32)
    out, _ = mamba2.mamba_block(x, lp, cfg, None)
    Di, N, H, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, groups
    h = mamba2.rms_norm(x, lp["ln"])
    z, xbc, dt = jnp.split(h @ lp["in_proj"], [Di, 2 * Di + 2 * G * N], axis=-1)
    xbc, _ = mamba2._causal_conv(xbc, lp["conv_w"], lp["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [Di, Di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y, _ = mamba2.ssd_reference(xs.reshape(2, 16, H, -1), dt, -jnp.exp(lp["A_log"]),
                                Bm.reshape(2, 16, G, N), Cm.reshape(2, 16, G, N))
    y = (y + xs.reshape(2, 16, H, -1) * lp["D_skip"][:, None]).reshape(2, 16, Di)
    g = (y * jax.nn.silu(z)).reshape(2, 16, G, Di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
    want = (g.reshape(2, 16, Di) * lp["gate_ln"]) @ lp["out_proj"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    state = {"conv": jnp.zeros((2, cfg.ssm_conv - 1, mamba2.conv_dim(cfg))),
             "ssm": jnp.zeros((2, H, cfg.ssm_headdim, N))}
    for t in range(16):
        o, state = mamba2.mamba_block(x[:, t:t + 1], lp, cfg, None, state)
        np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(want[:, t]), atol=2e-5)


def test_parameter_counts_published_and_cut():
    """7,356,749,648 and 2,733,050,240: HF's Zamba2ForCausalLM built from the
    published config.json and from the benchmark's 24-layer cut."""
    full = get_config("zamba2-7b")
    cut = fam.program_config(json.loads((CHIP / "configs" / "zamba2-7b.json").read_text()))
    for cfg, want in ((full, 7_356_749_648), (cut, 2_733_050_240)):
        tree = build_model(cfg).abstract_params()
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)) == want
        assert cfg.param_count() == want


HF_CHILD = r"""
import sys, numpy as np, torch
from transformers import Zamba2Config, Zamba2ForCausalLM
d = np.load(sys.argv[1])
ids = [int(i) for i in d["ids"]]
L = int(d["L"])
cfg = Zamba2Config(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, mamba_d_state=16, mamba_headdim=16, n_mamba_heads=8,
    chunk_size=int(d["chunk"]), adapter_rank=8, num_hidden_layers=L,
    layers_block_type=["hybrid" if i in ids else "mamba" for i in range(L)],
    vocab_size=256, num_mem_blocks=2, use_shared_attention_adapter=False,
    use_shared_mlp_adapter=True, use_mem_rope=True, mamba_ngroups=2, mamba_expand=2,
    mamba_d_conv=4, hidden_act="gelu", rms_norm_eps=1e-5, max_position_embeddings=64,
    add_bias_linear=False, use_conv_bias=True, tie_word_embeddings=True,
    time_step_min=0.001)
m = Zamba2ForCausalLM(cfg).eval()
sd = {k: torch.from_numpy(d[k]) for k in m.state_dict()}
m.load_state_dict(sd, strict=True)
with torch.no_grad():
    out = m(torch.from_numpy(d["tokens"]).long(), use_cache=False).logits.float().numpy()
np.save(sys.argv[2], out)
"""


def _hf_state(w, c):
    """The weights under HF's state-dict names (torch Linear: (out, in))."""
    s = fam.sizes(c)
    n = lambda a: np.asarray(a, np.float32)
    st = {"model.embed_tokens.weight": n(w["emb"]), "lm_head.weight": n(w["emb"]),
          "model.final_layernorm.weight": n(w["ln_f"])}
    for l in range(s["L"]):
        lp = jax.tree.map(lambda a: n(a[l]), w["layers"])
        pre = f"model.layers.{l}."
        if l in s["ids"]:
            j = s["ids"].index(l)
            bp = jax.tree.map(lambda a: n(a[j % s["NB"]]), w["blocks"])
            ap = jax.tree.map(lambda a: n(a[j]), w["apps"])
            st[pre + "linear.weight"] = ap["lin"].T
            sh = pre + "shared_transformer."
            st[sh + "input_layernorm.weight"] = bp["in_norm"]
            st[sh + "pre_ff_layernorm.weight"] = bp["ff_norm"]
            for q in "qkv":
                st[sh + f"self_attn.{q}_proj.weight"] = bp[f"w{q}"].T
            st[sh + "self_attn.o_proj.weight"] = bp["wo"].T
            ff = sh + "feed_forward."
            st[ff + "gate_up_proj.weight"] = np.concatenate([bp["w1"], bp["w3"]], 1).T
            st[ff + "down_proj.weight"] = bp["w2"].T
            st[ff + f"gate_up_proj_adapter_list.{j}.0.weight"] = ap["a"].T
            st[ff + f"gate_up_proj_adapter_list.{j}.1.weight"] = \
                np.concatenate([ap["b1"], ap["b3"]], 1).T
            pre += "mamba_decoder."
        st[pre + "input_layernorm.weight"] = lp["ln"]
        mm = pre + "mamba."
        st.update({mm + "in_proj.weight": lp["in_proj"].T,
                   mm + "conv1d.weight": lp["conv_w"].T[:, None, :],
                   mm + "conv1d.bias": lp["conv_b"], mm + "dt_bias": lp["dt_bias"],
                   mm + "A_log": lp["A_log"], mm + "D": lp["D_skip"],
                   mm + "norm.weight": lp["gate_ln"], mm + "out_proj.weight": lp["out_proj"].T})
    return st


def test_reference_matches_published_implementation(tmp_path):
    """The reference's logits against HF's Zamba2ForCausalLM (its torch path,
    float32) on the same seeded weights: both float32, so they differ by the
    order of operations only (HF's chunked scan, our recurrence).  HF's torch
    path carries the state from chunk to chunk wrongly (in
    ``Zamba2MambaMixer.torch_forward`` the decayed chunk states are summed
    over the target chunk, ``.sum(dim=2)``, where Mamba2's own torch path
    sums over the source): its logits leave the recurrence's from the second
    chunk on.  The chunk size is an algorithm's choice, not the model's, so
    HF runs here with one chunk over the whole sequence."""
    pytest.importorskip("torch")
    pytest.importorskip("transformers")
    c = zamba2_tiny.config()
    w = fam.make_weights(KEY, c, jnp.float32)
    toks = np.random.default_rng(5).integers(0, 256, (2, 20), dtype=np.int32)
    s = fam.sizes(c)
    np.savez(tmp_path / "w.npz", tokens=toks, ids=np.array(s["ids"]), L=s["L"],
             chunk=32, **_hf_state(w, c))
    env = dict(os.environ, USE_TF="0", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", HF_CHILD, str(tmp_path / "w.npz"),
                        str(tmp_path / "hf.npy")], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    hf = np.load(tmp_path / "hf.npy")
    ref = np.asarray(fam.logits(KEY, c, jnp.asarray(toks), np.arange(20),
                                dtype=jnp.float32))
    assert np.abs(ref).max() > 0.1
    np.testing.assert_allclose(ref, hf, rtol=0, atol=LOGIT_TOL)
