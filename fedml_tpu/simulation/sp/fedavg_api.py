"""Single-process FL simulation driving all federated optimizers.

Reference: ``simulation/sp/fedavg/fedavg_api.py:14`` (FedAvgAPI.train:66,
_client_sampling:127, _aggregate:144) plus the sibling per-algorithm APIs
(fedopt/fedprox/fednova/scaffold/feddyn/mime). Here one simulator covers
them all: the trainer factory picks the local algorithm and this class
applies the matching server rule. Client sampling reproduces the reference's
seeding exactly (``np.random.seed(round_idx)`` at fedavg_api.py:132) so runs
are comparable across frameworks.
"""

from __future__ import annotations

import copy
import logging
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ...constants import (
    FEDML_FEDERATED_OPTIMIZER_FEDDYN,
    FEDML_FEDERATED_OPTIMIZER_FEDNOVA,
    FEDML_FEDERATED_OPTIMIZER_FEDOPT,
    FEDML_FEDERATED_OPTIMIZER_MIME,
    FEDML_FEDERATED_OPTIMIZER_SCAFFOLD,
)
from ... import mlops
from ...core.aggregation.agg_operator import fednova_aggregate, scaffold_aggregate, uniform_average
from ...core.aggregation.server_optimizer import FedOptServer
from ...core.alg_frame.context import Context
from ...core.engine import (
    AlgFrameSink,
    InProcessSequentialStrategy,
    RoundCheckpointer,
    RoundEngine,
    sample_cohort,
)
from ...ml.aggregator import create_server_aggregator
from ...ml.trainer.trainer_creator import create_model_trainer
from ...utils.pytree import tree_sub, tree_zeros_like
from ..sp.client import Client
import jax

log = logging.getLogger(__name__)


class FedAvgAPI:
    def __init__(self, args: Any, device: Any, dataset, model, client_trainer=None, server_aggregator=None):
        self.device = device
        self.args = args
        [
            train_data_num,
            test_data_num,
            train_data_global,
            test_data_global,
            train_data_local_num_dict,
            train_data_local_dict,
            test_data_local_dict,
            class_num,
        ] = dataset
        self.train_global = train_data_global
        self.test_global = test_data_global
        self.train_data_num_in_total = train_data_num
        self.test_data_num_in_total = test_data_num
        self.train_data_local_num_dict = train_data_local_num_dict
        self.train_data_local_dict = train_data_local_dict
        self.test_data_local_dict = test_data_local_dict
        self.class_num = class_num
        self.fed_opt = str(getattr(args, "federated_optimizer", "FedAvg"))

        self.model_trainer = client_trainer or create_model_trainer(model, args)
        self.aggregator = server_aggregator or create_server_aggregator(copy.copy(model), args)
        Context().add(Context.KEY_TEST_DATA, self.test_global)

        self.client_list: List[Client] = []
        self._setup_clients(train_data_local_num_dict, train_data_local_dict, test_data_local_dict)

        # server-side algorithm state. create_fedopt_server returns the
        # mesh-sharded holder when args.server_mesh/FEDML_SERVER_MESH
        # resolves to >1 device (params + optimizer state live sharded and
        # the step runs fused on the mesh); on one device it is the plain
        # FedOptServer — identical to before.
        self._fedopt_server: Optional[FedOptServer] = None
        if self.fed_opt == FEDML_FEDERATED_OPTIMIZER_FEDOPT:
            from ...core.aggregation.server_optimizer import create_fedopt_server

            self._fedopt_server = create_fedopt_server(args, self.model_trainer.get_model_params())
        self._scaffold_c = tree_zeros_like(self.model_trainer.get_model_params())
        self._feddyn_h = tree_zeros_like(self.model_trainer.get_model_params())
        self._mime_s = tree_zeros_like(self.model_trainer.get_model_params())
        self.metrics_history: List[Dict[str, float]] = []

        # modelwatch (core.telemetry.modelwatch): fold-boundary delta stats
        # + contribution ledger for the default weight-space server rule.
        # Structured payloads (FedNova/SCAFFOLD/MIME) skip stats — their
        # uploads are not weight trees.
        self._mw_ledger = None
        self._mw_prev_update = None
        self._mw_round = 0
        from ...core.telemetry import modelwatch

        if modelwatch.enabled(args):
            self._mw_ledger = modelwatch.ContributionLedger()
            modelwatch.set_active(self._mw_ledger)

        # durable round state (core.resilience): every round boundary is
        # checkpointed async; --resume restarts from the last complete round
        self._round_store = None
        self._checkpointer: Optional[RoundCheckpointer] = None
        rdir = getattr(args, "resilience_dir", None)
        if rdir:
            from ...core.resilience import RoundStateStore

            self._round_store = RoundStateStore(str(rdir))
            self._checkpointer = RoundCheckpointer(self._round_store, args)

    def _setup_clients(self, train_data_local_num_dict, train_data_local_dict, test_data_local_dict) -> None:
        """One Client object per sampled slot, reused across rounds
        (reference fedavg_api.py:76-97: client objects are per-slot, local
        datasets swapped in per round)."""
        for client_idx in range(int(self.args.client_num_per_round)):
            c = Client(
                client_idx,
                train_data_local_dict[client_idx],
                test_data_local_dict[client_idx],
                train_data_local_num_dict[client_idx],
                self.args,
                self.device,
                self.model_trainer,
            )
            self.client_list.append(c)

    def _client_sampling(self, round_idx: int, client_num_in_total: int, client_num_per_round: int) -> List[int]:
        """Bit-exact mirror of reference _client_sampling (fedavg_api.py:127),
        now owned by the engine (core.engine.sample_cohort)."""
        return sample_cohort(round_idx, client_num_in_total, client_num_per_round)

    # --- durable round state ------------------------------------------
    def _round_state_dict(self, w_global) -> Dict[str, Any]:
        """The named pytrees a round boundary must persist: the global model
        plus whichever server-side algorithm state this optimizer carries."""
        st: Dict[str, Any] = {"model": w_global}
        if self.fed_opt == FEDML_FEDERATED_OPTIMIZER_SCAFFOLD:
            st["scaffold_c"] = self._scaffold_c
        elif self.fed_opt == FEDML_FEDERATED_OPTIMIZER_FEDDYN:
            st["feddyn_h"] = self._feddyn_h
        elif self.fed_opt == FEDML_FEDERATED_OPTIMIZER_MIME:
            st["mime_s"] = self._mime_s
        if self._fedopt_server is not None:
            st["fedopt"] = self._fedopt_server.state
        return st

    def _try_resume(self, w_global) -> Tuple[Any, int]:
        """Restore (w_global, start_round) from the round store when
        ``args.resume`` is set; (w_global, 0) otherwise."""
        if self._round_store is None or not getattr(self.args, "resume", False):
            return w_global, 0
        from ...core.resilience.round_state import restore_numpy_rng

        rs = self._round_store.resume(template=self._round_state_dict(w_global))
        if rs is None:
            return w_global, 0
        st = rs.state
        w_global = st["model"]
        if "scaffold_c" in st:
            self._scaffold_c = st["scaffold_c"]
        if "feddyn_h" in st:
            self._feddyn_h = st["feddyn_h"]
        if "mime_s" in st:
            self._mime_s = st["mime_s"]
        if self._fedopt_server is not None and "fedopt" in st:
            self._fedopt_server.state = st["fedopt"]
        restore_numpy_rng(rs.meta.get("numpy_rng"))
        tr = rs.meta.get("trainer_round")
        if tr is not None and hasattr(self.model_trainer, "_round"):
            self.model_trainer._round = int(tr)
        self.model_trainer.set_model_params(w_global)
        self.aggregator.set_model_params(w_global)
        mlops.log_resilience_event("resume", round_idx=rs.round_idx)
        return w_global, rs.round_idx + 1

    def _save_round_state(self, round_idx: int, w_global, cohort: List[int], *, final: bool = False) -> None:
        """Round-boundary durability, owned by the engine's RoundCheckpointer
        (drain-then-sync-save on the final round, chaos SIGKILL drills)."""
        if self._checkpointer is None:
            return
        self._checkpointer.save(
            int(round_idx),
            self._round_state_dict(w_global),
            cohort=cohort,
            extra_meta={"trainer_round": getattr(self.model_trainer, "_round", None)},
            final=final,
        )

    # ------------------------------------------------------------------
    def _build_execution(self):
        """Strategy + sink for the engine. ``--client_execution pipelined``
        swaps in the staged pipeline (core.pipeline): train/compress/fold
        overlap across the cohort, fold-at-arrival when the optimizer's
        semantics allow it (plain FedAvg, no middleware — bit-exact either
        way; see docs/pipeline.md), else pairs mode behind the same
        AlgFrameSink as the sequential path."""
        mode = str(getattr(self.args, "client_execution", "sequential") or "sequential")
        if mode == "pipelined":
            # lazy: core.pipeline pulls aggregation+compression, and the
            # engine package must stay an import-time leaf
            from ...core.pipeline import build_pipelined_execution

            return build_pipelined_execution(self)
        return InProcessSequentialStrategy(self), AlgFrameSink(self._server_update)

    def train(self) -> Dict[str, float]:
        strategy, sink = self._build_execution()
        engine = RoundEngine(
            self.args,
            strategy,
            sink,
            sample_fn=lambda r: self._client_sampling(
                r, int(self.args.client_num_in_total), int(self.args.client_num_per_round)
            ),
            install_fn=self._install_global,
            eval_fn=self._test_global,
            resume_fn=self._try_resume,
            checkpoint_fn=(self._save_round_state_cb if self._checkpointer is not None else None),
            finalize_fn=(lambda w: self._round_store.wait()) if self._round_store is not None else None,
            round_span_attrs={"optimizer": self.fed_opt},
            metrics_history=self.metrics_history,
        )
        w_global = self.model_trainer.get_model_params()
        params_view = getattr(self._fedopt_server, "params_view", None)
        if params_view is not None:
            # mesh-sharded server: start from its sharded view of the same
            # params, so round 0 runs on the layout every later round gets
            # back (else local_train compiles twice and round 0 sits on one
            # device)
            w_global = params_view()
            self._install_global(w_global)
        try:
            engine.run(w_global)
        finally:
            if self._mw_ledger is not None:
                from ...core.telemetry import modelwatch

                modelwatch.clear_active(self._mw_ledger)
        return self.metrics_history[-1] if self.metrics_history else {}

    def _install_global(self, w_global) -> None:
        self.model_trainer.set_model_params(w_global)
        self.aggregator.set_model_params(w_global)

    def _save_round_state_cb(self, round_idx: int, w_global, cohort: List[int], final: bool) -> None:
        self._save_round_state(round_idx, w_global, cohort, final=final)

    # ------------------------------------------------------------------
    def _server_update(self, w_global, w_locals):
        """Apply the per-algorithm server rule with the alg-frame hooks
        around it (reference fedavg_api._aggregate + per-alg APIs)."""
        agg = self.aggregator
        # Structured payloads (FedNova (a_i, d_i); SCAFFOLD (dw, dc)) must not
        # pass through the weight-space on_before hooks (defenses / cDP clip
        # assume plain weight pytrees) — they get their dedicated server rules.
        if self.fed_opt == FEDML_FEDERATED_OPTIMIZER_FEDNOVA:
            # d_i = (w_global - w_local)/a_i already carries lr (the local
            # steps applied it); no further scaling.
            new_w = fednova_aggregate(w_global, w_locals)
            new_w = agg.on_after_aggregation(new_w)
        elif self.fed_opt == FEDML_FEDERATED_OPTIMIZER_SCAFFOLD:
            new_w, self._scaffold_c = scaffold_aggregate(
                w_global,
                self._scaffold_c,
                w_locals,
                int(self.args.client_num_in_total),
                float(getattr(self.args, "server_lr", 1.0)),
            )
        elif self.fed_opt == FEDML_FEDERATED_OPTIMIZER_MIME:
            weight_payloads = [(n, p[0]) for n, p in w_locals]
            grad_payloads = [p[1] for _, p in w_locals]
            lst = agg.on_before_aggregation(weight_payloads)
            new_w = agg.aggregate(lst)
            new_w = agg.on_after_aggregation(new_w)
            beta = float(getattr(self.args, "mime_beta", 0.9))
            avg_grad = uniform_average(grad_payloads)
            self._mime_s = jax.tree.map(lambda s, g: beta * s + (1 - beta) * g, self._mime_s, avg_grad)
        elif self.fed_opt == FEDML_FEDERATED_OPTIMIZER_FEDDYN:
            lst = agg.on_before_aggregation(w_locals)
            alpha = float(getattr(self.args, "feddyn_alpha", 0.01))
            avg_w = uniform_average([w for _, w in lst])
            m = int(self.args.client_num_in_total)
            # uniform mean of (w_i - g) == mean(w_i) - g: reuse avg_w instead
            # of a second K-tree aggregation pass
            delta = tree_sub(avg_w, w_global)
            frac = len(lst) / float(m)
            self._feddyn_h = jax.tree.map(lambda h, d: h - alpha * frac * d, self._feddyn_h, delta)
            new_w = jax.tree.map(lambda w, h: w - h / alpha, avg_w, self._feddyn_h)
            new_w = agg.on_after_aggregation(new_w)
        else:
            lst = agg.on_before_aggregation(w_locals)
            watch = self._mw_session(w_global)
            if watch is not None:
                from ...core.telemetry import modelwatch

                lst = modelwatch.screen_cohort(
                    watch, lst, list(range(len(lst))),
                    ledger=self._mw_ledger,
                    quarantine=modelwatch.quarantine_enabled(self.args))
            new_w = agg.aggregate(lst)
            if self._fedopt_server is not None:
                new_w = self._fedopt_server.apply(w_global, new_w)
            new_w = agg.on_after_aggregation(new_w)
            if watch is not None:
                try:
                    stats = watch.finish(new_w)
                    self._mw_prev_update = stats.update_tree
                    self._mw_ledger.observe_round(self._mw_round, stats)
                except Exception:  # noqa: BLE001 - stats must never break the fold
                    log.debug("modelwatch: round stats failed", exc_info=True)
                self._mw_round += 1
        agg.assess_contribution()
        return new_w

    def _mw_session(self, w_global):
        """A per-round modelwatch session over the current global params, or
        None when disabled (or the tree has non-array leaves)."""
        if self._mw_ledger is None:
            return None
        from ...core.telemetry import modelwatch

        try:
            return modelwatch.WatchSession(w_global, prev_update=self._mw_prev_update)
        except Exception:  # noqa: BLE001 - object leaves (FHE ciphertexts) etc.
            return None

    # ------------------------------------------------------------------
    def _test_global(self, round_idx: int) -> Dict[str, float]:
        metrics = self.aggregator.test(self.test_global, self.device, self.args)
        metrics["round"] = round_idx
        log.info("round %d: %s", round_idx, {k: round(float(v), 4) for k, v in metrics.items()})
        return metrics

    def _local_test_on_all_clients(self, round_idx: int) -> Dict[str, float]:
        """reference fedavg_api.py:176 — average local test metrics."""
        train_metrics = {"num_samples": [], "num_correct": [], "losses": []}
        test_metrics = {"num_samples": [], "num_correct": [], "losses": []}
        client = self.client_list[0]
        for client_idx in range(int(self.args.client_num_in_total)):
            if self.test_data_local_dict.get(client_idx) is None:
                continue
            client.update_local_dataset(
                client_idx,
                self.train_data_local_dict[client_idx],
                self.test_data_local_dict[client_idx],
                self.train_data_local_num_dict[client_idx],
            )
            tm = client.local_test(False)
            train_metrics["num_samples"].append(tm["test_total"])
            train_metrics["num_correct"].append(tm["test_correct"])
            train_metrics["losses"].append(tm["test_loss"] * tm["test_total"])
            sm = client.local_test(True)
            test_metrics["num_samples"].append(sm["test_total"])
            test_metrics["num_correct"].append(sm["test_correct"])
            test_metrics["losses"].append(sm["test_loss"] * sm["test_total"])
        out = {
            "round": round_idx,
            "train_acc": sum(train_metrics["num_correct"]) / max(sum(train_metrics["num_samples"]), 1),
            "train_loss": sum(train_metrics["losses"]) / max(sum(train_metrics["num_samples"]), 1),
            "test_acc": sum(test_metrics["num_correct"]) / max(sum(test_metrics["num_samples"]), 1),
            "test_loss": sum(test_metrics["losses"]) / max(sum(test_metrics["num_samples"]), 1),
        }
        log.info("local test round %d: %s", round_idx, out)
        return out
