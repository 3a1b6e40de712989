"""Kind `bivariate`: a 2-metric job judged against the 2-D Gaussian of its
aligned history (`models/bivariate.py`; `docs/guides/design.md:78`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.engine import scoring
from foremast_tpu.engine.judge import bucket_length
from foremast_tpu.engine.kinds.base import ArenaKind, pack_bf16_delta_rows
from foremast_tpu.models.bivariate import (
    detect_bivariate,
    detect_bivariate_from_rows,
    detect_bivariate_from_rows_sharded,
    fit_bivariate,
    fit_bivariate_bf16_delta,
)


def _pack_np(rows: list[np.ndarray], length: int) -> tuple[np.ndarray, np.ndarray]:
    """Ragged rows -> host ([B, length] values, [B, length] mask)."""
    b = len(rows)
    out = np.zeros((b, length), np.float32)
    mask = np.zeros((b, length), bool)
    for i, r in enumerate(rows):
        n = min(len(r), length)
        out[i, :n] = r[:n]
        mask[i, :n] = True
    return out, mask


def _pack(rows: list[np.ndarray], length: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ragged rows -> ([B, length] values, [B, length] mask) on device."""
    out, mask = _pack_np(rows, length)
    return jnp.asarray(out), jnp.asarray(mask)


class BivariateKind(ArenaKind):
    name = "bivariate"
    selectors = {"bivariate_normal": (2, 2), "auto": (2, 2)}

    def cache_key(self, config, app, aliases, hist_keys, tc) -> tuple:
        # history identity IS part of the key: two live docs for the
        # same app/aliases over different historical ranges (two
        # deployments) must never share a fitted Gaussian
        return (self.name, app, aliases, hist_keys)

    def admissible(self, judge, entry, meta) -> bool:
        return (
            entry is not None
            and meta[5] >= judge.config.min_historical_points
        )

    # Slow-path bivariate stage: fit + dispatch + gather + verdict
    # decode in one body (cold-fit latency regime; the warm path is
    # judge_warm).
    # foremast: device-boundary
    def judge_cold(self, judge, jobs: list) -> list:
        threshold = judge.config.anomaly.rule_for(None).threshold
        min_pts = judge.config.min_historical_points
        # pairwise evidence is computed for EVERY job — even ones that end
        # up UNKNOWN — so the wire always carries it (univariate parity)
        all_joints = [judge._joint(job_tasks) for job_tasks in jobs]
        all_pw = judge._pairwise(all_joints)
        joints, pw, out = [], [], []
        for j, p in zip(all_joints, all_pw):
            if len(j.hist_t) < min_pts or len(j.cur_t) == 0:
                out.extend(judge._unknown(j.tasks, p))
            else:
                joints.append(j)
                pw.append(p)
        if not joints:
            return out

        th = bucket_length(max(len(j.hist_t) for j in joints))
        tc = bucket_length(max(len(j.cur_t) for j in joints))
        hx_np, hm_np = _pack_np([j.hist_v[0] for j in joints], th)
        hy_np, _ = _pack_np([j.hist_v[1] for j in joints], th)
        cx, cm = _pack([j.cur_v[0] for j in joints], tc)
        cy, _ = _pack([j.cur_v[1] for j in joints], tc)

        eff_thr = judge._effective_thresholds(pw, threshold)
        if scoring.bf16_delta_enabled():
            # cold joint fits ship anchor + bf16 deltas (2 B/point) —
            # the same wire layout as the univariate cold-fit upload
            ax, dx = pack_bf16_delta_rows(hx_np, hm_np)
            ay, dy = pack_bf16_delta_rows(hy_np, hm_np)
            fit = fit_bivariate_bf16_delta(
                jnp.asarray(ax),
                jnp.asarray(dx),
                jnp.asarray(ay),
                jnp.asarray(dy),
                jnp.asarray(hm_np),
                min_points=min_pts,
            )
        else:
            fit = fit_bivariate(
                jnp.asarray(hx_np),
                jnp.asarray(hy_np),
                jnp.asarray(hm_np),
                min_points=min_pts,
            )
        flags = np.asarray(detect_bivariate(fit, cx, cy, cm, jnp.asarray(eff_thr)))
        valid = np.asarray(fit.valid)
        mean_np = np.asarray(fit.mean)
        cov_np = np.asarray(fit.cov)
        for i, j in enumerate(joints):
            if not valid[i]:
                out.extend(judge._unknown(j.tasks, pw[i]))
            else:
                # valid fits become warm-path state: the entry is the
                # fitted Gaussian, the meta carries the warm-band inputs
                # (invalid fits cache NOTHING, so the columnar path can
                # never turn an UNKNOWN doc healthy)
                judge._record_joint(
                    self, j, 0, entry=(mean_np[i], cov_np[i])
                )
                out.extend(
                    judge._emit(
                        j, flags[i, : len(j.cur_t)], float(eff_thr[i]), pw[i]
                    )
                )
        return out

    def template(self, f: int = 2, m: int = 1):
        sd = jax.ShapeDtypeStruct
        return {
            "mean": sd((2,), jnp.float32),
            "cov": sd((2, 2), jnp.float32),
        }

    def row_tree(self, entry, m: int):
        return {"mean": entry[0], "cov": entry[1]}

    def operands(self, entries, thr, f, sb, s0, cur, mask, gaps):
        return (cur[:, 0], cur[:, 1], mask), (np.full(sb, thr, np.float32),)

    def program(self, state, rows, *args):
        return detect_bivariate_from_rows(
            state["mean"], state["cov"], rows, *args
        )

    def program_sharded(self, state, rows, *args, mesh):
        return detect_bivariate_from_rows_sharded(
            state["mean"], state["cov"], rows, *args, mesh=mesh
        )
