"""Distributed launcher: `python -m paddle_tpu.distributed.launch ... train.py`.

Reference parity: python/paddle/distributed/launch/main.py:23 (CLI), the
collective controller (launch/controllers/collective.py:22,:267 — builds the
per-rank PADDLE_* env and watches pods) and the elastic restart behavior
(fleet/elastic/manager.py:125; launch --elastic_level).

TPU-native shape: the deployment unit is one PROCESS PER HOST (jax SPMD
single controller per host; devices of a host belong to one process), so
--nnodes/--nproc_per_node spawn host-controller processes. Rendezvous is
MASTER_ADDR/PORT + the C++ TCPStore (store.cpp) — the same store the
framework's host collectives and checkpoint coordination use. Failure
policy: any worker dying restarts the whole job generation (the reference's
collective controller also resets peers on membership change) up to
--max_restarts times.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def parse_args(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="launch a distributed training job "
                    "(reference: paddle.distributed.launch, main.py:23)")
    p.add_argument("--nnodes", type=str, default="1",
                   help="number of host-controller processes to launch. "
                        "Elastic form MIN:MAX (reference --nnodes 2:4 / "
                        "elastic manager scale semantics): starts MAX "
                        "ranks; when ranks die, the next generation "
                        "relaunches with the surviving count (never below "
                        "MIN) and workers resume from their distributed "
                        "checkpoint under the new world size "
                        "(reshard-on-load)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes per node (reference-CLI parity). "
                        "On a TPU host exactly ONE process owns all local "
                        "chips, so values > 1 are rejected unless the ranks "
                        "run on CPU (JAX_PLATFORMS=cpu) — scale TPU jobs "
                        "with --nnodes / --rank_offset instead")
    p.add_argument("--master", default=None,
                   help="host:port of the rendezvous store "
                        "(default: 127.0.0.1:<free port>)")
    p.add_argument("--rank_offset", type=int, default=0,
                   help="first global rank hosted by this launcher "
                        "(multi-machine: run one launcher per machine)")
    p.add_argument("--world_size", type=int, default=None,
                   help="total ranks across machines (default: local ranks)")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="job-generation restarts before giving up "
                        "(reference --elastic_level analog)")
    p.add_argument("--log_dir", default=None, help="per-rank log directory")
    p.add_argument("--run_mode", default="collective",
                   help="collective (default) or ps (spawns --server_num "
                        "table servers + trainers; ranks see PS_ROLE / "
                        "PADDLE_MASTER and use distributed.rpc + "
                        "distributed.ps)")
    p.add_argument("--server_num", type=int, default=1,
                   help="ps mode: number of table-server processes "
                        "(reference --server_num)")
    p.add_argument("--trainer_num", type=int, default=None,
                   help="ps mode: trainer processes (default: "
                        "nproc_per_node)")
    p.add_argument("training_script", help="script (or -m module) to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class Controller:
    """Spawns rank processes with the PADDLE_* env, watches them, and
    restarts the generation on failure (collective.py:267 Watcher analog)."""

    def __init__(self, args):
        if args.run_mode not in ("collective", "ps"):
            raise NotImplementedError(
                f"run_mode={args.run_mode!r}: collective and ps exist "
                "(rpc workers launch as collective ranks + distributed.rpc)")
        self.args = args
        # --nnodes N or MIN:MAX (elastic)
        nn = str(args.nnodes)
        if ":" in nn:
            lo, hi = nn.split(":", 1)
            self.min_nodes, self.max_nodes = int(lo), int(hi)
            if not 1 <= self.min_nodes <= self.max_nodes:
                raise SystemExit(f"--nnodes {nn}: need 1 <= MIN <= MAX")
            self.elastic = True
        else:
            self.min_nodes = self.max_nodes = int(nn)
            self.elastic = False
        args.nnodes = self.max_nodes
        self.ps_servers = 0
        if args.run_mode == "ps":
            trainers = args.trainer_num if args.trainer_num is not None \
                else args.nproc_per_node
            self.ps_servers = args.server_num
            args.nproc_per_node = self.ps_servers + trainers
        self.nranks_local = args.nnodes * args.nproc_per_node
        if self.nranks_local > 1 and \
                os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
            # one process owns all local TPU chips; a second would fail or
            # hang waiting for them (the reference's per-GPU model does not
            # transfer). --nnodes N also means N processes on THIS machine.
            raise SystemExit(
                f"--nnodes={args.nnodes} x --nproc_per_node="
                f"{args.nproc_per_node} starts {self.nranks_local} "
                "processes here: a TPU host runs ONE worker process (jax "
                "owns every local chip). Run one launcher per machine "
                "with --nnodes 1 --rank_offset/--world_size, or set "
                "JAX_PLATFORMS=cpu if these ranks are CPU-only (e.g. ps "
                "servers/trainers, elastic drills).")
        self.world = args.world_size or self.nranks_local
        master = args.master or f"127.0.0.1:{_free_port()}"
        self.master_addr, self.master_port = master.rsplit(":", 1)
        # Store port must be the SAME on every machine of the job. With an
        # explicit --master (multi-machine) derive it deterministically
        # (master_port+1, store.py's default); single-machine default-master
        # launches can instead grab a verified-free local port.
        self.store_port = (int(self.master_port) + 1) if args.master \
            else _free_port()
        self.procs: List[subprocess.Popen] = []
        self._logs: List = []
        self.generation = 0

    def _env(self, rank: int) -> dict:
        env = dict(os.environ)
        endpoints = ",".join(
            f"{self.master_addr}:{int(self.master_port) + 1 + r}"
            for r in range(self.world))
        env.update({
            "MASTER_ADDR": self.master_addr,
            "MASTER_PORT": str(self.master_port),
            "PADDLE_STORE_PORT": str(self.store_port),
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(self.world),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_RESTART_GENERATION": str(self.generation),
            "RANK": str(rank),
            "WORLD_SIZE": str(self.world),
        })
        if self.args.run_mode == "ps":
            env["PS_ROLE"] = "server" if rank < self.ps_servers else "trainer"
            # rpc hosts its own store on the master port (no jax.distributed
            # coordinator in a CPU ps job; the global TCPStore, if any, uses
            # PADDLE_STORE_PORT)
            env["PADDLE_MASTER"] = f"{self.master_addr}:{self.master_port}"
        return env

    def _spawn_rank(self, rank: int) -> subprocess.Popen:
        cmd = [sys.executable, self.args.training_script,
               *self.args.training_script_args]
        stdout = None
        if self.args.log_dir:
            os.makedirs(self.args.log_dir, exist_ok=True)
            stdout = open(os.path.join(
                self.args.log_dir,
                f"rank{rank}.gen{self.generation}.log"), "wb")
            self._logs.append(stdout)
        return subprocess.Popen(cmd, env=self._env(rank), stdout=stdout,
                                stderr=subprocess.STDOUT if stdout else None)

    def _spawn_all(self):
        self.procs = [self._spawn_rank(self.args.rank_offset + i)
                      for i in range(self.nranks_local)]

    def _kill_all(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in self.procs:
            try:
                p.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        for f in self._logs:
            try:
                f.close()
            except OSError:
                pass
        self._logs.clear()

    def run(self) -> int:
        self._spawn_all()
        while True:
            time.sleep(0.2)
            codes = [p.poll() for p in self.procs]
            if all(c == 0 for c in codes):
                for f in self._logs:
                    f.close()
                self._logs.clear()
                return 0
            failed = [i for i, c in enumerate(codes)
                      if c is not None and c != 0]
            if failed:
                if self.elastic:
                    # settle window: co-failing ranks exit staggered; the
                    # survivor count must reflect the whole generation's
                    # outcome, not the first poll that saw a failure
                    deadline = time.time() + 5.0
                    while time.time() < deadline and any(
                            p.poll() is None for p in self.procs):
                        time.sleep(0.2)
                    codes = [p.poll() for p in self.procs]
                    failed = [i for i, c in enumerate(codes)
                              if c is not None and c != 0]
                rank = self.args.rank_offset + failed[0]
                if self.generation >= self.args.max_restarts:
                    sys.stderr.write(
                        f"[launch] rank {rank} failed "
                        f"(rc={codes[failed[0]]}); max_restarts="
                        f"{self.args.max_restarts} exhausted\n")
                    self._kill_all()
                    return 1
                self.generation += 1
                if self.elastic and self.args.world_size is None:
                    # elastic scale-in: continue with the surviving NODES
                    # (reference ElasticManager scale decision,
                    # fleet/elastic/manager.py:218-293); a node is dead
                    # when any of its ranks failed. Workers resume from
                    # the distributed checkpoint under the new world size
                    # via reshard-on-load.
                    nproc = self.args.nproc_per_node
                    cur_nodes = self.nranks_local // nproc
                    dead_nodes = {i // nproc for i in failed}
                    new_nodes = cur_nodes - len(dead_nodes)
                    if new_nodes < self.min_nodes:
                        sys.stderr.write(
                            f"[launch] {len(dead_nodes)} node(s) failed; "
                            f"{new_nodes} survivors < min_nodes="
                            f"{self.min_nodes}; giving up\n")
                        self._kill_all()
                        return 1
                    if new_nodes != cur_nodes:
                        sys.stderr.write(
                            f"[launch] elastic scale-down: world "
                            f"{self.world} -> {new_nodes * nproc}\n")
                        self.nranks_local = new_nodes * nproc
                        self.world = self.nranks_local
                sys.stderr.write(
                    f"[launch] rank {rank} failed (rc={codes[failed[0]]}); "
                    f"restarting generation {self.generation}\n")
                self._kill_all()
                self._spawn_all()


def main(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    ctl = Controller(args)

    def _forward(sig, frame):
        ctl._kill_all()
        sys.exit(128 + sig)

    signal.signal(signal.SIGTERM, _forward)
    signal.signal(signal.SIGINT, _forward)
    return ctl.run()
