"""The comparison that decides ``correct``.

Served tokens are SAMPLED (temperature 1): speculative sampling draws every
random number from the round's PRNG key, so the reference can replay each
decision the timed path made with the same numbers and its own float32
logits, along the served sequence:

  draft      the draft's top-vhat distribution at every drafted position
             (the values the timed path uploaded) against the reference
             draft's: ``draft_logq_err``, the widest |log q - log q_ref|
             over tokens that carry more than 1e-3 of either;
  accept     each accept test ``u < min(1, p/q)`` on the served sequence:
             ``accept_gap``, the widest distance |u - min(1, p_ref/q_ref)|
             of a test the reference decides the other way (0 if none).
             In token-tree rounds (J > 1) the uniforms are drawn per tree
             node; ``tree_paths`` rebuilds the node order from the round's
             drafts, so the winning path's tests are replayed (the other
             paths leave the served sequence and are not);
  residual   the token drawn by inverse CDF from normalize(max(p - q, 0)) at
             the first rejection: ``resid_gap``, how far u lies outside the
             reference's CDF interval of the served token (0 if inside);
  bonus      the token drawn by Gumbel-max from the target's logits after a
             full acceptance: ``bonus_gap``, by how much the served token's
             perturbed log-probability lies below the reference's best;
  tokens     ``token_mismatch``, served tokens that differ from the tokens
             the rounds committed (exact);
  all        ``flip_share``, the share of the accept tests, residual draws
             and bonus draws that the reference decides the other way: a
             mean over some thousand decisions, steadier than a widest gap.

A number is compared where the configuration gives it a limit
(``limits``), and printed otherwise.  A gap is the distance by which the reference's own logits would have had to
be off to make the served decision; it reads 0 where the two agree.  The
control puts a lower precision in the program's place and makes the same
decisions from its own logits with the same random numbers.

Every decision of the program lies on the served sequence: the prefix a
decision conditions on is the prompt plus the tokens committed before it.

The reference is ``references/<reference>.py``, named by the configuration
(one for both models; a pair of two architectures gives one that tells them
apart by each section's ``"arch"``).  The check calls two functions of it:

    hidden_states(params, section, tokens, precision)
        final hidden states (N, T, d) of token rows (N, T), float32;
    sequence_log_probs(params, x_row, precision)
        (T, V) log-softmax of the next token at every position of one row
        from its final hidden states (T, d).

``precision`` is ``"f32"`` for the reference and ``"fp8"`` for the control.
The weights are the architecture's own seeded ones (``arch.of(section)
.make_params`` with the run's keys), regenerated here after the program's
are freed.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

import arch
import weights

THRESH = 1e-3           # a token counts in the draft check above this mass
LOG_CAP = 60.0          # |log q| differences are capped here
# every number the check reads; a configuration's ``limits`` name the ones
# it compares
NUMBERS = ("draft_logq_err", "accept_gap", "resid_gap", "bonus_gap",
           "flip_share", "token_mismatch")
GAPS = ("draft_logq_err", "accept_gap", "resid_gap", "bonus_gap")


@dataclasses.dataclass
class Decisions:
    """Decision records of one sequence, host arrays padded to K rows."""
    d_pos: list = dataclasses.field(default_factory=list)
    d_idx: list = dataclasses.field(default_factory=list)
    d_val: list = dataclasses.field(default_factory=list)
    a_pos: list = dataclasses.field(default_factory=list)
    a_tok: list = dataclasses.field(default_factory=list)
    a_u: list = dataclasses.field(default_factory=list)
    a_acc: list = dataclasses.field(default_factory=list)
    r_pos: list = dataclasses.field(default_factory=list)
    r_tok: list = dataclasses.field(default_factory=list)
    r_u: list = dataclasses.field(default_factory=list)
    b_pos: list = dataclasses.field(default_factory=list)
    b_tok: list = dataclasses.field(default_factory=list)
    b_key: list = dataclasses.field(default_factory=list)
    b_row: list = dataclasses.field(default_factory=list)
    committed: list = dataclasses.field(default_factory=list)

    def padded(self, K: int, vhat: int, B: int) -> dict:
        def pad(x, shape, dtype):
            out = np.zeros((K,) + shape, dtype)
            if x:
                out[:len(x)] = np.asarray(x, dtype).reshape((len(x),) + shape)
            return out

        n = {k: len(getattr(self, k + "_pos")) for k in "darb"}
        if max(n.values()) > K:
            raise ValueError(f"more decisions than positions: {n} > {K}")
        return {
            "d_pos": pad(self.d_pos, (), np.int32),
            "d_idx": pad(self.d_idx, (vhat,), np.int32),
            "d_val": pad(self.d_val, (vhat,), np.float32),
            "d_ok": np.arange(K) < n["d"],
            "a_pos": pad(self.a_pos, (), np.int32),
            "a_tok": pad(self.a_tok, (), np.int32),
            "a_u": pad(self.a_u, (), np.float32),
            "a_acc": pad(self.a_acc, (), bool),
            "a_ok": np.arange(K) < n["a"],
            "r_pos": pad(self.r_pos, (), np.int32),
            "r_tok": pad(self.r_tok, (), np.int32),
            "r_u": pad(self.r_u, (), np.float32),
            "r_ok": np.arange(K) < n["r"],
            "b_pos": pad(self.b_pos, (), np.int32),
            "b_tok": pad(self.b_tok, (), np.int32),
            "b_key": pad(self.b_key, (2,), np.uint32),
            "b_row": pad(self.b_row, (), np.int32),
            "b_ok": np.arange(K) < n["b"],
        }


def _round_uniforms(rounds):
    """Per round: (u_accept, u_resid (B,), bonus key).  u_accept is (B, L)
    per draft position, or (B, J * L) per tree node in token-tree rounds."""
    out = []
    for r in rounds:
        key = jnp.asarray(r.key)
        _, k_verify = jax.random.split(key)
        ka, kr, kb = jax.random.split(k_verify, 3)
        B = len(r.lengths)
        L = (r.out["output_tokens"].shape[1] - 1 if r.J == 1
             else r.J * int(np.max(r.lengths)))
        ua = np.asarray(jax.random.uniform(ka, (B, L)))
        out.append((ua, np.asarray(jax.random.uniform(kr, (B,))),
                    np.asarray(jax.random.key_data(kb)
                               if jnp.issubdtype(kb.dtype,
                                                 jax.dtypes.prng_key)
                               else kb)))
    return out


def tree_paths(drafts: np.ndarray, length: int) -> np.ndarray:
    """Node index of every draft position of one row's token tree: drafts
    (J, L) are merged on shared prefixes, and nodes are numbered in the
    order they are first met, draft by draft, position by position."""
    node_of, paths = {}, np.zeros((drafts.shape[0], length), np.int64)
    for j, draft in enumerate(drafts):
        parent = -1
        for pos in range(length):
            parent = node_of.setdefault((parent, int(draft[pos])),
                                        len(node_of))
            paths[j, pos] = parent
    return paths


def decisions_of(rid: int, seq_len: int, served: list, rounds, uniforms):
    """The decisions the program made for request ``rid`` whose prefix lies
    inside the served sequence (``seq_len`` tokens, prompt included)."""
    dec = Decisions()
    for r, (ua, ur, kb) in zip(rounds, uniforms):
        for b, (rr, i0) in r.live.items():
            if rr != rid:
                continue
            n = int(r.out["accept_counts"][b])
            Lk = int(r.lengths[b])
            out_tok = r.out["output_tokens"][b]
            dec.committed += [int(t) for t in out_tok[:n + 1]]
            w = int(r.out["winner"][b]) if "winner" in r.out else None
            if w is not None:
                node = tree_paths(r.out["tokens"][b], Lk)[w]
            for j in range(1, min(n + 1, Lk) + 1):
                pos = i0 + j - 1
                if pos >= seq_len:
                    break
                if w is None:
                    qi, qv = r.out["q_idx"][b, j - 1], r.out["q_val"][b, j - 1]
                    tok, u = r.out["tokens"][b, j - 1], ua[b, j - 1]
                else:
                    qi = r.out["q_idx"][b, w, j - 1]
                    qv = r.out["q_val"][b, w, j - 1]
                    tok = r.out["tokens"][b, w, j - 1]
                    u = ua[b, node[j - 1]]
                dec.d_pos.append(pos)
                dec.d_idx.append(qi)
                dec.d_val.append(qv)
                dec.a_pos.append(pos)
                dec.a_tok.append(int(tok))
                dec.a_u.append(float(u))
                dec.a_acc.append(j <= n)
            pos = i0 + (n if n < Lk else Lk)
            if pos >= seq_len:
                continue
            if n < Lk:
                dec.r_pos.append(pos)
                dec.r_tok.append(int(out_tok[n]))
                dec.r_u.append(float(ur[b]))
            else:
                dec.b_pos.append(pos)
                dec.b_tok.append(int(out_tok[n]))
                dec.b_key.append(kb)
                dec.b_row.append(b)
    mismatch = sum(int(a != b) for a, b in zip(served, dec.committed))
    mismatch += max(0, len(served) - len(dec.committed))
    return dec, mismatch


def _top(lpD, vhat):
    vals, idx = jax.lax.top_k(lpD, vhat)
    return idx, vals - jax.nn.logsumexp(vals, axis=-1, keepdims=True)


def _lookup(idx_row, lq_row, tokens):
    """log q of ``tokens`` in one top-vhat row; -inf where absent."""
    hit = tokens[..., None] == idx_row
    return jnp.where(hit.any(-1),
                     jnp.sum(jnp.where(hit, lq_row, 0.0), -1), -jnp.inf)


@functools.partial(jax.jit, static_argnames=("B", "vhat", "control"))
def gaps(lpT, lpD, dec, lpT_c, lpD_c, *, B: int, vhat: int, control: bool):
    """Widest gap of each kind for one sequence.  The candidate is the
    program (its recorded decisions) or, with ``control``, the control's
    logits making the same decisions."""
    ridx, rlq = _top(lpD, vhat)                     # reference draft
    if control:
        cidx, clq = _top(lpD_c, vhat)

    def draft_err(e):
        pos, ci, cv, ok = e
        ri, rq = ridx[pos], jnp.exp(rlq[pos])
        if control:
            ci, cv = cidx[pos], jnp.exp(clq[pos])
        c_ref = jnp.exp(_lookup(ri, rlq[pos], ci))
        r_cand = jnp.exp(_lookup(ci, jnp.log(jnp.maximum(cv, 1e-38)), ri))

        def err(a, b):
            big = jnp.maximum(a, b) > THRESH
            d = jnp.abs(jnp.log(jnp.maximum(a, 1e-30))
                        - jnp.log(jnp.maximum(b, 1e-30)))
            return jnp.max(jnp.where(big, jnp.minimum(d, LOG_CAP), 0.0))

        return jnp.where(ok, jnp.maximum(err(cv, c_ref), err(r_cand, rq)), 0.0)

    def accept_gap(e):
        pos, tok, u, acc, ok = e
        lq = _lookup(ridx[pos], rlq[pos], tok)
        ratio = jnp.minimum(1.0, jnp.exp(lpT[pos, tok] - lq))
        if control:
            lqc = _lookup(cidx[pos], clq[pos], tok)
            acc = u < jnp.minimum(1.0, jnp.exp(lpT_c[pos, tok] - lqc))
        flip = ok & (acc != (u < ratio))
        return jnp.where(flip, jnp.abs(u - ratio), 0.0), flip

    def residual(lp, idx, lq, u):
        p = jnp.exp(lp)
        q = jnp.zeros_like(p).at[idx].add(jnp.exp(lq))
        r = jnp.maximum(p - q, 0.0)
        z = jnp.sum(r)
        cdf = jnp.cumsum(r)
        tok = jnp.minimum(jnp.sum(cdf <= u * z), p.shape[0] - 1)
        return jnp.where(z <= 0, jnp.argmax(p), tok), r, cdf, z

    def resid_gap(e):
        pos, tok, u, ok = e
        x_ref, r, cdf, z = residual(lpT[pos], ridx[pos], rlq[pos], u)
        if control:
            tok = residual(lpT_c[pos], cidx[pos], clq[pos], u)[0]
        hi, lo = cdf[tok], cdf[tok] - r[tok]
        t = u * z
        d = jnp.maximum(jnp.maximum(lo - t, t - hi), 0.0) / jnp.maximum(z, 1e-30)
        flip = ok & (tok != x_ref)
        return jnp.where(flip, d, 0.0), flip

    def bonus_gap(e):
        pos, tok, key, row, ok = e
        g = jax.random.gumbel(key, (B, lpT.shape[-1]), jnp.float32)[row]
        s = lpT[pos] + g
        if control:
            tok = jnp.argmax(lpT_c[pos] + g)
        gap = jnp.where(ok, jnp.max(s) - s[tok], 0.0)
        return gap, ok & (tok != jnp.argmax(s))

    m = functools.partial(jax.lax.map, batch_size=32)
    a_gap, a_flip = m(accept_gap, (dec["a_pos"], dec["a_tok"], dec["a_u"],
                                   dec["a_acc"], dec["a_ok"]))
    r_gap, r_flip = m(resid_gap, (dec["r_pos"], dec["r_tok"], dec["r_u"],
                                  dec["r_ok"]))
    b_gap, b_flip = m(bonus_gap, (dec["b_pos"], dec["b_tok"], dec["b_key"],
                                  dec["b_row"], dec["b_ok"]))
    return {
        "draft_logq_err": jnp.max(m(draft_err, (dec["d_pos"], dec["d_idx"],
                                                dec["d_val"], dec["d_ok"]))),
        "accept_gap": jnp.max(a_gap),
        "resid_gap": jnp.max(r_gap),
        "bonus_gap": jnp.max(b_gap),
        "flips": jnp.sum(a_flip) + jnp.sum(r_flip) + jnp.sum(b_flip),
    }


def check(config: dict, seed: int, samples: list, rounds, T_max: int,
          precisions=("program",)) -> dict:
    """Replay ``samples`` — (rid, prompt, served tokens) of finished
    requests — against the float32 reference.  Returns, per candidate in
    ``precisions`` ("program", "fp8"), the widest reading of each number
    over the samples (the share of flipped decisions over all of them),
    and the decision counts."""
    vhat = int(config["serving"]["vhat"])
    uniforms = _round_uniforms(rounds)
    seqs, decs, mismatch = [], [], 0
    for rid, prompt, served in samples:
        seq = list(prompt) + list(served)
        if len(seq) > T_max:
            raise ValueError(f"sequence of {len(seq)} tokens > T_max {T_max}")
        dec, mm = decisions_of(rid, len(seq), served, rounds, uniforms)
        seqs.append(seq)
        decs.append(dec)
        mismatch += mm
    tokens = np.zeros((len(seqs), T_max), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    B = len(rounds[0].lengths)
    ref = importlib.import_module(f"references.{config['reference']}")
    kt, kd = weights.pair_keys(seed)
    acc = config["acceptance"]
    hidden = {}
    for role, key in (("target", kt), ("draft", kd)):
        params = arch.of(config[role]).make_params(
            config[role], acc, key, config["serving"]["dtype"])
        for prec in ("f32",) + tuple(p for p in precisions if p != "program"):
            hidden[role, prec] = (ref.hidden_states(params, config[role],
                                                      tokens, prec), params)
        del params
    result = {}
    counts = {k: sum(len(getattr(d, k + "_pos")) for d in decs)
              for k in "darb"}
    for cand in precisions:
        worst, flips = {k: 0.0 for k in GAPS}, 0
        for i, dec in enumerate(decs):
            lp = {}
            for role in ("target", "draft"):
                for prec in {"f32", cand if cand != "program" else "f32"}:
                    h, params = hidden[role, prec]
                    lp[role, prec] = ref.sequence_log_probs(params, h[i],
                                                              prec)
            c = cand if cand != "program" else "f32"
            got = gaps(lp["target", "f32"], lp["draft", "f32"],
                       {k: jnp.asarray(v) for k, v in
                        dec.padded(T_max, vhat, B).items()},
                       lp["target", c], lp["draft", c], B=B, vhat=vhat,
                       control=cand != "program")
            flips += int(got.pop("flips"))
            for k, v in got.items():
                worst[k] = max(worst[k], float(v))
        worst["flip_share"] = flips / max(counts["a"] + counts["r"]
                                          + counts["b"], 1)
        worst["token_mismatch"] = float(mismatch if cand == "program" else 0)
        result[cand] = worst
    result["counts"] = {"sequences": len(seqs), "draft": counts["d"],
                        "accept": counts["a"], "residual": counts["r"],
                        "bonus": counts["b"],
                        "served_tokens": sum(len(s[2]) for s in samples)}
    return result
