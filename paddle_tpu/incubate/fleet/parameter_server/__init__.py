"""Fleet parameter-server mode (transpiler-based).

Reference: python/paddle/fluid/incubate/fleet/parameter_server/
distribute_transpiler/__init__.py — fleet facade over
DistributeTranspiler: init_worker/init_server/run_server +
ParameterServerOptimizer.  TPU-native: the pserver is the C++ table
service (distributed_ps/), trainers talk to it through host ops on the
executor's hybrid path; dense tables apply the optimizer server-side
(configured from the stripped optimize ops, like pslib downpour tables).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ....framework.core import default_main_program, default_startup_program
from ....transpiler.distribute_transpiler import (
    DistributeTranspiler,
    DistributeTranspilerConfig,
)
from ..base.fleet_base import DistributedOptimizer, Fleet, Mode


def _optimizer_cfg_from_ops(opt_ops, param_name, lr_value) -> dict:
    for op_ in opt_ops:
        rv = op_.attr("op_role_var")
        if rv and rv[0] == param_name:
            t = op_.type
            if t == "sgd":
                return {"optimizer": "sgd", "lr": lr_value}
            if t == "momentum":
                return {"optimizer": "momentum", "lr": lr_value,
                        "mu": op_.attr("mu", 0.9)}
            if t == "adam":
                return {"optimizer": "adam", "lr": lr_value,
                        "beta1": op_.attr("beta1", 0.9),
                        "beta2": op_.attr("beta2", 0.999),
                        "eps": op_.attr("epsilon", 1e-8)}
            if t == "adagrad":
                return {"optimizer": "adagrad", "lr": lr_value,
                        "eps": op_.attr("epsilon", 1e-6)}
    return {"optimizer": "sgd", "lr": lr_value}


class FleetTranspiler(Fleet):
    """reference: parameter_server/distribute_transpiler/__init__.py."""

    def __init__(self):
        super().__init__(Mode.TRANSPILER)
        self._transpiler: Optional[DistributeTranspiler] = None
        self._origin_lr = 0.01
        self.main_program = None
        self.startup_program = None
        self._servers = []
        self._client = None

    # ------------------------------------------------------------------
    def distributed_optimizer(self, optimizer, strategy=None):
        self._origin_lr = float(getattr(optimizer, "_learning_rate", 0.01)) \
            if not callable(getattr(optimizer, "_learning_rate", None)) else 0.01
        self._optimizer = ParameterServerOptimizer(optimizer, strategy, self)
        return self._optimizer

    # ------------------------------------------------------------------
    def init_worker(self):
        """Connect the PS client; trainer 0 pushes initial params."""
        from ....distributed_ps import runtime
        from ....distributed_ps.service import PSClient

        eps = self.server_endpoints()
        self._client = PSClient(eps)
        runtime.set_client(self._client, self.worker_index(),
                           heartbeat_interval=5.0)
        t = self._transpiler
        # create tables on servers
        block = t.origin_program.global_block()
        for p, g in t._param_grads:
            var = block._find_var_recursive(p)
            size = int(np.prod([abs(s) for s in var.shape]))
            cfg = _optimizer_cfg_from_ops(t._opt_ops, p, self._origin_lr)
            self._client.create_dense(p, size, **cfg)
        # sparse embedding tables (downpour-style: rows materialize on
        # first pull server-side; no trainer init push)
        for tname, dim in getattr(t, "_sparse_tables", {}).items():
            cfg = _optimizer_cfg_from_ops(t._opt_ops, tname, self._origin_lr)
            self._client.create_sparse(tname, dim, **cfg)
        if self.worker_index() == 0:
            # push locally-initialized params (reference: trainer0 bcast)
            from ....framework.scope import global_scope

            scope = global_scope()
            for p, g in t._param_grads:
                val = scope.get(p)
                if val is not None:
                    self._client.init_dense(p, np.asarray(val).ravel())

        # install the communicator for async / half-async / GEO modes
        # (reference: Communicator::InitInstance + fleet init_worker)
        from ....transpiler.distribute_transpiler import DistributedMode
        from ....distributed_ps.communicator import (
            AsyncCommunicator, GeoSgdCommunicator, HalfAsyncCommunicator)

        mode = getattr(t, "mode", DistributedMode.SYNC)
        if mode == DistributedMode.SYNC and self.worker_num() > 1:
            # init_dense overwrites the table: a worker that pushed its
            # first gradients before trainer 0's values arrived would
            # lose them.  Sync rounds already meet at barriers, so every
            # worker is there to meet at this one (reference: trainer0
            # bcast + barrier).
            self._client.barrier()
        if mode == DistributedMode.ASYNC:
            runtime.set_communicator(
                AsyncCommunicator(self._client).start())
        elif mode == DistributedMode.HALF_ASYNC:
            runtime.set_communicator(
                HalfAsyncCommunicator(self._client).start())
        elif mode == DistributedMode.GEO:
            comm = GeoSgdCommunicator(
                self._client, [p for p, _ in t._param_grads],
                push_nums=getattr(t.config, "geo_sgd_need_push_nums", 100),
                sparse_tables=getattr(t, "_sparse_tables", {}))
            # baseline snapshots = the just-initialized params (what the
            # server holds after trainer-0's init push); start() then
            # pulls baselines for any param missing from the scope
            from ....framework.scope import global_scope
            comm.init_snapshots(global_scope())
            runtime.set_communicator(comm.start())

    def init_server(self, model_dir=None, endpoint=None):
        from ....distributed_ps.service import PSServer

        ep = endpoint or self.server_endpoints()[self.server_index()]
        server = PSServer(ep, n_trainers=self.worker_num())
        self._servers.append(server)
        if model_dir:
            server._load(model_dir)
        return server

    def run_server(self, block=False):
        for s in self._servers:
            s.start(block=block)
        return self._servers

    def stop_worker(self):
        from ....distributed_ps import runtime

        runtime.clear()
        if self._client is not None:
            self._client.close()

    def save_persistables(self, executor=None, dirname="./ps_model",
                          main_program=None):
        self._client.save(dirname)

    def load_persistables(self, executor=None, dirname="./ps_model"):
        self._client.load(dirname)


fleet = FleetTranspiler()


class ParameterServerOptimizer(DistributedOptimizer):
    def __init__(self, optimizer, strategy=None, fleet_=None):
        super().__init__(optimizer,
                         strategy or DistributeTranspilerConfig())
        self._fleet = fleet_

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        optimize_ops, params_grads = self._optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set)
        f = self._fleet
        config = self._strategy if isinstance(
            self._strategy, DistributeTranspilerConfig) else DistributeTranspilerConfig()
        t = DistributeTranspiler(config)
        sync = getattr(config, "sync_mode", True)
        t.transpile(
            trainer_id=f.worker_index() if f._is_initialized else 0,
            program=loss.block.program,
            pservers=",".join(f.server_endpoints()) if f._is_initialized
            else "127.0.0.1:6174",
            trainers=f.worker_num() if f._is_initialized else 1,
            sync_mode=sync,
            mode=config.distributed_mode,
        )
        f._transpiler = t
        f.main_program = t.origin_program
        f.startup_program = startup_program or default_startup_program()
        return optimize_ops, params_grads
