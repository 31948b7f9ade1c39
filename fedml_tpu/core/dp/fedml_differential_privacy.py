"""Differential-privacy facade.

Reference: ``python/fedml/core/dp/fedml_differential_privacy.py:13`` —
singleton configured from args, invoked only from the alg-frame hooks:
``add_local_noise`` (LDP, client-side, client_trainer.py:59), ``global_clip``
+ ``add_global_noise`` (cDP, server-side, server_aggregator.py:90-103).

The actual DP logic lives in a *frame* selected by ``args.dp_solution_type``
(frames/: GlobalDP "cdp", LocalDP "ldp", NbAFLDP "nbafl", DPClip "dp_clip"),
mirroring the reference's frames/{cdp,ldp,NbAFL,dp_clip}.py.

One RDP accountant lives here and is stepped automatically on every noising
call (the reference splits accounting between the facade and GlobalDP and
neither path is driven end-to-end).
"""

from __future__ import annotations

import logging
import threading
from typing import Any, List, Optional, Tuple

import jax

from ...utils.pytree import PyTree
from .budget_accountant.rdp_accountant import RDPAccountant
from .frames import create_dp_frame
from .frames.cdp import GlobalDP
from .frames.ldp import LocalDP

DP_SOLUTION_CDP = "cdp"
DP_SOLUTION_LDP = "ldp"
DP_SOLUTION_NBAFL = "nbafl"
DP_SOLUTION_DP_CLIP = "dp_clip"

_LOCAL_SOLUTIONS = (DP_SOLUTION_LDP, DP_SOLUTION_NBAFL, DP_SOLUTION_DP_CLIP)
_GLOBAL_SOLUTIONS = (DP_SOLUTION_CDP, DP_SOLUTION_NBAFL, DP_SOLUTION_DP_CLIP)


class FedMLDifferentialPrivacy:
    _instance: Optional["FedMLDifferentialPrivacy"] = None
    _instance_lock = threading.Lock()

    @classmethod
    def get_instance(cls) -> "FedMLDifferentialPrivacy":
        # parties of one process (the in-memory cross-silo harness) call
        # fedml.init from threads: one instance, built once. Unlocked, two
        # threads each built one (a device PRNG key apiece), the loser was
        # dropped while the other thread still configured it, and the process
        # segfaulted about once in a hundred runs of tests/test_cross_silo.py
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def __init__(self) -> None:
        self.is_enabled = False
        self.dp_solution = None
        self.frame = None
        self.accountant = None
        self.sample_rate = 1.0
        self._key = jax.random.PRNGKey(0)

    def init(self, args: Any) -> None:
        self.is_enabled = bool(getattr(args, "enable_dp", False))
        if not self.is_enabled:
            return
        self.dp_solution = str(getattr(args, "dp_solution_type", DP_SOLUTION_CDP)).lower()
        if self.dp_solution == "dpclip":
            self.dp_solution = DP_SOLUTION_DP_CLIP
        self.frame = create_dp_frame(args)
        # one clipping knob: args.clipping_norm feeds the frame's per-client
        # global-norm clip unless the frame clips its own way (NbAFL/DPClip)
        # or max_grad_norm was set explicitly.
        clipping_norm = getattr(args, "clipping_norm", None)
        if (
            clipping_norm is not None
            and self.frame.max_grad_norm is None
            and isinstance(self.frame, (GlobalDP, LocalDP))
        ):
            self.frame.max_grad_norm = float(clipping_norm)
        self.accountant = RDPAccountant()
        self.sample_rate = float(getattr(args, "client_num_per_round", 1)) / float(
            getattr(args, "client_num_in_total", 1)
        )
        self._key = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)) + 7)
        logging.info(
            "DP enabled: solution=%s clip=%s", self.dp_solution, self.frame.max_grad_norm
        )

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    # --- enable predicates (queried from hooks) -------------------------
    def is_dp_enabled(self) -> bool:
        return self.is_enabled

    def is_local_dp_enabled(self) -> bool:
        return self.is_enabled and self.dp_solution in _LOCAL_SOLUTIONS

    def is_global_dp_enabled(self) -> bool:
        return self.is_enabled and self.dp_solution in _GLOBAL_SOLUTIONS

    def is_central_dp_enabled(self) -> bool:
        return self.is_global_dp_enabled()

    def is_clipping(self) -> bool:
        return self.is_enabled and self.frame is not None and self.frame.max_grad_norm is not None

    # --- noising (reference :88-103) ------------------------------------
    def add_local_noise(self, local_grad: PyTree, extra_auxiliary_info: Any = None) -> PyTree:
        """Client-side perturbation. ``extra_auxiliary_info`` is a dict the
        alg-frame hook fills with ``global_model_params`` (the round's model
        as received, needed by DP-Clip's delta clipping) and
        ``local_sample_num`` (NbAFL's m)."""
        if isinstance(self.frame, LocalDP) and self.frame.max_grad_norm is not None:
            local_grad = self.frame.global_clip([(1.0, local_grad)])[0][1]
        return self.frame.add_local_noise(local_grad, self._next_key(), extra_auxiliary_info)

    def add_global_noise(self, global_model: PyTree) -> PyTree:
        out = self.frame.add_global_noise(global_model, self._next_key())
        if not isinstance(self.frame, LocalDP):
            self._account_step()
        return out

    def global_clip(self, raw_client_grad_list: List[Tuple[float, PyTree]]) -> List[Tuple[float, PyTree]]:
        """Called from on_before_aggregation whenever DP is on: feeds round
        statistics to the frame, accounts one LDP composition per *round*
        (per-client stepping would inflate epsilon L-fold), then clips if
        configured."""
        self.frame.set_params_for_dp(raw_client_grad_list)
        if isinstance(self.frame, LocalDP):
            # LDP clips client-side *before* noising; re-clipping the noised
            # models here would rescale signal+noise and break calibration.
            self._account_step()
            return raw_client_grad_list
        return self.frame.global_clip(raw_client_grad_list)

    # --- accounting ------------------------------------------------------
    def _account_step(self, steps: int = 1) -> None:
        sigma = self.frame.get_rdp_scale() if self.frame is not None else None
        if self.accountant is not None and sigma:
            self.accountant.step(noise_multiplier=sigma, sample_rate=self.sample_rate, steps=steps)

    def account(self, *, sample_rate: float, steps: int = 1) -> None:
        """Manual accounting entry point (e.g. per-local-step LDP)."""
        if self.accountant is not None and self.frame is not None:
            sigma = self.frame.get_rdp_scale()
            if sigma:
                self.accountant.step(noise_multiplier=sigma, sample_rate=sample_rate, steps=steps)

    def get_epsilon(self, delta: float = 1e-5) -> float:
        return self.accountant.get_epsilon(delta) if self.accountant else float("inf")
