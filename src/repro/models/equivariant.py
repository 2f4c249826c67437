"""NequIP-style E(3)-equivariant GNN (l_max = 2) in Cartesian form.

Irreps are represented in Cartesian tensors (equivalent to real spherical
harmonics up to an orthogonal change of basis, which preserves equivariance):

    l=0  scalars             (N, C0)
    l=1  vectors             (N, C1, 3)
    l=2  symmetric traceless (N, C2, 3, 3)

Edge "spherical harmonics": Y0 = 1, Y1 = r_hat, Y2 = r_hat r_hat^T - I/3.
Tensor-product paths (l1 x l2 -> l3) use closed Cartesian forms (dot, cross,
matvec, symmetric-traceless outer/anticommutator, Levi-Civita contraction),
weighted per channel by a radial MLP over n_rbf Bessel bases with a smooth
polynomial cutoff — the NequIP interaction block. Gates: scalars pass through
SiLU; l>0 features are gated by sigmoid(scalar channels).

Equivariance under proper rotations SO(3) (rotate inputs => outputs rotate
accordingly; energies invariant) is asserted in tests/test_models_gnn.py.
Parity (O(3) reflections) is not tracked per channel — cross-product paths mix
pseudo/true tensors; strict-NequIP parity bookkeeping is noted as a deviation
in DESIGN.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["init_nequip", "nequip_forward", "nequip_energy_loss"]

_EPS = 1e-9
_I3 = np.eye(3, dtype=np.float32)   # a host constant: no device at import


# ---------------------------------------------------------------- tensor ops
def sym_traceless(m: jnp.ndarray) -> jnp.ndarray:
    s = 0.5 * (m + jnp.swapaxes(m, -1, -2))
    tr = jnp.trace(s, axis1=-2, axis2=-1)[..., None, None]
    return s - tr * _I3 / 3.0


def _levi_civita_contract(m: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """(2 x 2 -> 1): v_i = eps_{ijk} (M N)_{jk}."""
    mn = m @ n
    return jnp.stack([mn[..., 1, 2] - mn[..., 2, 1],
                      mn[..., 2, 0] - mn[..., 0, 2],
                      mn[..., 0, 1] - mn[..., 1, 0]], axis=-1)


# ------------------------------------------------------------------- radial
def bessel_basis(r: jnp.ndarray, n_rbf: int, cutoff: float) -> jnp.ndarray:
    """(E,) -> (E, n_rbf) sinc-like Bessel bases with polynomial cutoff."""
    r = jnp.maximum(r, _EPS)
    n = jnp.arange(1, n_rbf + 1, dtype=r.dtype)
    basis = jnp.sqrt(2.0 / cutoff) * jnp.sin(
        n * jnp.pi * r[:, None] / cutoff) / r[:, None]
    # smooth cutoff envelope (p=6 polynomial, NequIP eq. 8)
    u = jnp.clip(r / cutoff, 0.0, 1.0)
    env = 1 - 28 * u**6 + 48 * u**7 - 21 * u**8
    return basis * env[:, None]


def _mlp(key, dims, dtype):
    ks = jax.random.split(key, len(dims) - 1)
    return [{"w": (jax.random.normal(k, (a, b)) * a ** -0.5).astype(dtype),
             "b": jnp.zeros((b,), dtype)}
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def _apply_mlp(layers, x):
    for i, p in enumerate(layers):
        x = x @ p["w"] + p["b"]
        if i < len(layers) - 1:
            x = jax.nn.silu(x)
    return x


# -------------------------------------------------------------------- model
# tensor-product paths used per interaction: (l_in, l_sh, l_out)
_PATHS = [(0, 0, 0), (0, 1, 1), (0, 2, 2),
          (1, 0, 1), (1, 1, 0), (1, 1, 1), (1, 1, 2), (1, 2, 1),
          (2, 0, 2), (2, 1, 1), (2, 2, 0), (2, 1, 2), (2, 2, 1), (2, 2, 2)]


def init_nequip(key, cfg, n_species: int = 16) -> dict:
    """cfg: GNNConfig(kind='nequip') with extras l_max, n_rbf, cutoff."""
    dt = cfg.param_dtype
    c = cfg.d_hidden                       # channels per l
    n_rbf = cfg.extra("n_rbf", 8)
    keys = jax.random.split(key, cfg.n_layers * 2 + 3)
    layers = []
    for i in range(cfg.n_layers):
        kr, ks = jax.random.split(keys[i])
        layers.append({
            # radial MLP emits one weight per (path, channel)
            "radial": _mlp(kr, (n_rbf, 32, len(_PATHS) * c), dt),
            # per-l self-interaction channel mixers
            "mix0": (jax.random.normal(ks, (c, c)) * c ** -0.5).astype(dt),
            "mix1": (jax.random.normal(jax.random.fold_in(ks, 1), (c, c))
                     * c ** -0.5).astype(dt),
            "mix2": (jax.random.normal(jax.random.fold_in(ks, 2), (c, c))
                     * c ** -0.5).astype(dt),
            # gate scalars for l=1 and l=2
            "gate": (jax.random.normal(jax.random.fold_in(ks, 3), (c, 2 * c))
                     * c ** -0.5).astype(dt),
        })
    return {
        "species_embed": (jax.random.normal(keys[-1], (n_species, c))
                          * 0.1).astype(dt),
        "layers": layers,
        "readout": _mlp(keys[-2], (c, 32, 1), dt),
    }


def _tp_accumulate(feats, sh, w, c):
    """Weighted tensor products of node feats with edge harmonics.

    feats: dict l -> per-edge gathered features (E, C[, 3[, 3]])
    sh:    dict l -> edge harmonics (E[, 3[, 3]])
    w:     (E, n_paths, C) radial weights
    Returns per-edge messages dict l -> (E, C, ...).
    """
    e = w.shape[0]
    out = {0: jnp.zeros((e, c)),
           1: jnp.zeros((e, c, 3)),
           2: jnp.zeros((e, c, 3, 3))}
    y1 = sh[1][:, None, :]                     # (E, 1, 3)
    y2 = sh[2][:, None, :, :]                  # (E, 1, 3, 3)
    x0, x1, x2 = feats[0], feats[1], feats[2]

    for pi, (li, ls, lo) in enumerate(_PATHS):
        wp = w[:, pi, :]                       # (E, C)
        if (li, ls, lo) == (0, 0, 0):
            r = x0
        elif (li, ls, lo) == (0, 1, 1):
            r = x0[..., None] * y1
        elif (li, ls, lo) == (0, 2, 2):
            r = x0[..., None, None] * y2
        elif (li, ls, lo) == (1, 0, 1):
            r = x1
        elif (li, ls, lo) == (1, 1, 0):
            r = jnp.einsum("eci,ei->ec", x1, sh[1])
        elif (li, ls, lo) == (1, 1, 1):
            r = jnp.cross(x1, jnp.broadcast_to(y1, x1.shape))
        elif (li, ls, lo) == (1, 1, 2):
            outer = x1[..., :, None] * y1[..., None, :]
            r = sym_traceless(outer)
        elif (li, ls, lo) == (1, 2, 1):
            r = jnp.einsum("eij,ecj->eci", sh[2], x1)
        elif (li, ls, lo) == (2, 0, 2):
            r = x2
        elif (li, ls, lo) == (2, 1, 1):
            r = jnp.einsum("ecij,ej->eci", x2, sh[1])
        elif (li, ls, lo) == (2, 2, 0):
            r = jnp.einsum("ecij,eij->ec", x2, sh[2])
        elif (li, ls, lo) == (2, 1, 2):
            # T_ij = sym_traceless( eps_iab y_a M_bj ): cross y with columns
            mc = jnp.swapaxes(x2, -1, -2)              # (E, C, j, b)
            yb = jnp.broadcast_to(y1[:, :, None, :], mc.shape)
            crossed = jnp.cross(yb, mc)                # (E, C, j, i)
            r = sym_traceless(jnp.swapaxes(crossed, -1, -2))
        elif (li, ls, lo) == (2, 2, 1):
            r = _levi_civita_contract(x2, jnp.broadcast_to(y2, x2.shape))
        elif (li, ls, lo) == (2, 2, 2):
            anti = x2 @ y2 + y2 @ x2
            r = sym_traceless(anti)
        else:  # pragma: no cover
            raise AssertionError((li, ls, lo))
        if lo == 0:
            out[0] = out[0] + wp * r
        elif lo == 1:
            out[1] = out[1] + wp[..., None] * r
        else:
            out[2] = out[2] + wp[..., None, None] * r
    return out


def nequip_forward(params: dict, cfg, batch: dict) -> jnp.ndarray:
    """batch: positions (N,3), species (N,), edge_index (2,E),
    node_graph (N,), n_graphs. Returns per-graph energies (n_graphs,)."""
    pos = batch["positions"]
    src, dst = batch["edge_index"]
    n = pos.shape[0]
    c = cfg.d_hidden
    cutoff = cfg.extra("cutoff", 5.0)
    n_rbf = cfg.extra("n_rbf", 8)

    rel = pos[src] - pos[dst]                          # (E, 3)
    dist = jnp.linalg.norm(rel + _EPS, axis=-1)
    r_hat = rel / jnp.maximum(dist, _EPS)[:, None]
    sh = {0: jnp.ones_like(dist),
          1: r_hat,
          2: sym_traceless(r_hat[:, :, None] * r_hat[:, None, :])}
    rbf = bessel_basis(dist, n_rbf, cutoff)

    feats = {0: params["species_embed"][batch["species"]],
             1: jnp.zeros((n, c, 3)),
             2: jnp.zeros((n, c, 3, 3))}

    def interact(lp, feats):
        w = _apply_mlp(lp["radial"], rbf).reshape(-1, len(_PATHS), c)
        gathered = {0: feats[0][src], 1: feats[1][src], 2: feats[2][src]}
        msg = _tp_accumulate(gathered, sh, w, c)
        agg = {l: jax.ops.segment_sum(msg[l], dst, num_segments=n)
               for l in (0, 1, 2)}
        # self-interaction + residual
        h0 = feats[0] + agg[0] @ lp["mix0"]
        h1 = feats[1] + jnp.einsum("ncI,cd->ndI", agg[1], lp["mix1"])
        h2 = feats[2] + jnp.einsum("ncIJ,cd->ndIJ", agg[2], lp["mix2"])
        # gated nonlinearity
        gates = jax.nn.sigmoid(h0 @ lp["gate"])        # (N, 2C)
        return {0: jax.nn.silu(h0),
                1: h1 * gates[:, :c, None],
                2: h2 * gates[:, c:, None, None]}

    # (remat per block was tried and refuted — see EXPERIMENTS.md §Perf 6b)
    for lp in params["layers"]:
        feats = interact(lp, feats)

    energy_per_node = _apply_mlp(params["readout"], feats[0])[:, 0]
    return jax.ops.segment_sum(energy_per_node, batch["node_graph"],
                               num_segments=batch["n_graphs"])


def nequip_energy_loss(params, cfg, batch) -> jnp.ndarray:
    e = nequip_forward(params, cfg, batch)
    return jnp.mean((e - batch["labels"].astype(e.dtype)) ** 2)
