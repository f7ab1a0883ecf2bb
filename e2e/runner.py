"""Process-isolated testnet runner: setup -> start -> load -> perturb ->
invariant tests -> benchmark -> cleanup.

Reference model: test/e2e/runner/{setup,start,load,perturb,test,
benchmark}.go.  Each node is a real OS process (`python -m
cometbft_tpu.cmd start`) with its own home dir, talking real TCP p2p and
JSON-RPC on localhost; perturbations are signals (SIGKILL/SIGSTOP/
SIGCONT) and restarts, like the reference's docker `kill`/`pause`
perturbations (test/e2e/runner/perturb.go:47-91).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
from dataclasses import dataclass, field

from e2e import loadtime
from e2e.manifest import Manifest, NodeManifest, load_manifest
from e2e.rpc_client import NodeRPC, RPCError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@dataclass
class RunningNode:
    manifest: NodeManifest
    home: str
    rpc_laddr: str
    p2p_laddr: str
    node_id: str = ""
    proc: subprocess.Popen | None = None
    log_path: str = ""
    app_proc: subprocess.Popen | None = None  # socket/grpc ABCI app
    app_laddr: str = ""
    upgraded: bool = False  # the "upgrade" perturbation is one-shot
    env_extra: dict = field(default_factory=dict)

    @property
    def rpc(self) -> NodeRPC:
        return NodeRPC(self.rpc_laddr)


_APP_SERVER_SNIPPET = """
import sys, time
sys.path.insert(0, {repo!r})
from cometbft_tpu.abci.kvstore import KVStoreApplication
{import_line}
srv = {server_expr}
srv.start()
print("abci app listening", flush=True)
while True:
    time.sleep(1)
"""


class Testnet:
    def __init__(self, manifest: Manifest, workdir: str):
        self.manifest = manifest
        self.workdir = workdir
        self.nodes: list[RunningNode] = []

    # -- setup ------------------------------------------------------------

    def setup(self) -> None:
        """Generate per-node homes sharing one genesis (reference:
        runner/setup.go)."""
        from cometbft_tpu.config import config as cfgmod
        from cometbft_tpu.node.nodekey import NodeKey
        from cometbft_tpu.privval.file_pv import FilePV
        from cometbft_tpu.types.basic import Timestamp
        from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator

        pvs = {}
        for nm in self.manifest.nodes:
            if nm.key_type != "ed25519":
                # FilePV generation is ed25519-only today; failing loudly
                # beats silently running the wrong key type
                raise NotImplementedError(
                    f"{nm.name}: e2e validator key_type {nm.key_type!r} "
                    "not supported (FilePV generates ed25519)"
                )
            home = os.path.join(self.workdir, nm.name)
            cfg = cfgmod.default_config()
            cfg.base.home = home
            cfg.rpc.laddr = f"tcp://127.0.0.1:{_free_port()}"
            cfg.p2p.laddr = f"tcp://127.0.0.1:{_free_port()}"
            cfg.base.db_backend = "sqlite"  # must survive kill -9
            cfg.consensus.timeout_commit_ms = 200
            cfg.consensus.timeout_propose_ms = 2000
            if nm.zone:
                cfg.p2p.zone = nm.zone
                cfg.p2p.zone_rtt_ms = self.manifest.zones
                # peer_zones is filled in the second pass (node ids below)
            if nm.abci_protocol in ("socket", "grpc"):
                app_port = _free_port()
                cfg.base.abci = nm.abci_protocol
                cfg.base.proxy_app = f"tcp://127.0.0.1:{app_port}"
            if nm.state_sync:
                if nm.start_at == 0:
                    raise ValueError(
                        f"{nm.name}: state_sync requires start_at > 0 "
                        "(a fresh late joiner)"
                    )
                # enable + trust parameters are filled in at join time
                # from the live network (start_late_joiners)
            cfgmod.write_config(cfg)
            pv = FilePV.load_or_generate(
                os.path.join(home, cfg.base.priv_validator_key_file),
                os.path.join(home, cfg.base.priv_validator_state_file),
            )
            nk = NodeKey.load_or_generate(
                os.path.join(home, cfg.base.node_key_file)
            )
            node = RunningNode(
                manifest=nm,
                home=home,
                rpc_laddr=cfg.rpc.laddr,
                p2p_laddr=cfg.p2p.laddr,
                node_id=nk.node_id,
                log_path=os.path.join(home, "node.log"),
            )
            self.nodes.append(node)
            if nm.mode == "validator":
                pvs[nm.name] = pv

        gdoc = GenesisDoc(
            chain_id=self.manifest.chain_id,
            genesis_time=Timestamp.now(),
            initial_height=self.manifest.initial_height,
            validators=[
                GenesisValidator(pv.pub_key(), 10) for pv in pvs.values()
            ],
        )
        peers = [
            f"{n.node_id}@{n.p2p_laddr.split('://', 1)[-1]}"
            for n in self.nodes
        ]
        for i, node in enumerate(self.nodes):
            gpath = os.path.join(node.home, "config", "genesis.json")
            with open(gpath, "w") as f:
                f.write(gdoc.to_json())
            # full mesh of persistent peers minus self (small testnets)
            cfg = cfgmod.load_config(node.home)
            cfg.p2p.persistent_peers = [
                p for j, p in enumerate(peers) if j != i
            ]
            if cfg.p2p.zone:
                cfg.p2p.peer_zones = {
                    n.node_id: n.manifest.zone
                    for n in self.nodes
                    if n.manifest.zone and n.node_id != node.node_id
                }
            cfgmod.write_config(cfg)

    # -- start / stop -----------------------------------------------------

    @staticmethod
    def _child_env() -> dict:
        env = dict(os.environ)
        # a chip belongs to one process: the testnet's nodes stay on the CPU
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        return env

    def _maybe_start_app(self, node: RunningNode) -> None:
        """For socket/grpc ABCI manifests: the app is its own OS process
        (the reference's separate-container app), serving kvstore."""
        proto = node.manifest.abci_protocol
        if proto == "builtin" or (
            node.app_proc is not None and node.app_proc.poll() is None
        ):
            return
        from cometbft_tpu.config import config as cfgmod

        cfg = cfgmod.load_config(node.home)
        addr = cfg.base.proxy_app
        if proto == "socket":
            import_line = "from cometbft_tpu.abci.server import ABCIServer"
            server_expr = f"ABCIServer(KVStoreApplication(), {addr!r})"
        else:
            import_line = (
                "from cometbft_tpu.abci.grpc_abci import GRPCABCIServer"
            )
            server_expr = f"GRPCABCIServer(KVStoreApplication(), {addr!r})"
        code = _APP_SERVER_SNIPPET.format(
            repo=REPO, import_line=import_line, server_expr=server_expr
        )
        with open(node.log_path.replace(".log", "-app.log"), "ab") as logf:
            node.app_proc = subprocess.Popen(
                [sys.executable, "-c", code],
                stdout=logf,
                stderr=subprocess.STDOUT,
                env=self._child_env(),
                cwd=REPO,
            )
        node.app_laddr = addr
        # wait until the app actually listens — the subprocess pays a
        # multi-second interpreter+jax import before binding, longer on a
        # loaded machine (a fixed sleep here was a flake source)
        hostport = addr.split("://", 1)[-1]
        host, _, port = hostport.rpartition(":")
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if node.app_proc.poll() is not None:
                raise RuntimeError(
                    f"{node.manifest.name}: ABCI app process exited "
                    f"rc={node.app_proc.returncode}"
                )
            try:
                socket.create_connection((host, int(port)), timeout=1).close()
                return
            except OSError:
                time.sleep(0.2)
        raise TimeoutError(
            f"{node.manifest.name}: ABCI app never listened on {addr}"
        )

    def start_node(self, node: RunningNode) -> None:
        self._maybe_start_app(node)
        # the 'ab' handle is only for Popen inheritance; the child keeps
        # its own duplicate, so close ours (no fd leak across restarts)
        with open(node.log_path, "ab") as logf:
            env = self._child_env()
            env.update(node.env_extra)
            node.proc = subprocess.Popen(
                [sys.executable, "-m", "cometbft_tpu.cmd",
                 "--home", node.home, "start"],
                stdout=logf,
                stderr=subprocess.STDOUT,
                env=env,
                cwd=REPO,
            )

    def start(self, timeout: float = 120.0) -> None:
        for node in self.nodes:
            if node.manifest.start_at == 0:
                self.start_node(node)
        deadline = time.monotonic() + timeout
        for node in self.nodes:
            if node.proc is None:
                continue
            retries = 2  # _free_port is bind/close/reuse: a stolen port
            while time.monotonic() < deadline:  # shows as instant exit
                if node.rpc.is_up():
                    break
                if node.proc.poll() is not None:
                    if retries > 0:
                        retries -= 1
                        time.sleep(0.5)
                        self.start_node(node)
                        continue
                    raise RuntimeError(
                        f"{node.manifest.name} exited rc={node.proc.returncode}"
                        f" (log: {node.log_path})"
                    )
                time.sleep(0.25)
            else:
                raise TimeoutError(f"{node.manifest.name} RPC never came up")

    def stop(self) -> None:
        for node in self.nodes:
            for proc in (node.proc, node.app_proc):
                if proc and proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
        for node in self.nodes:
            for proc in (node.proc, node.app_proc):
                if proc is None:
                    continue
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=5)

    # -- phases -----------------------------------------------------------

    def wait_height(self, h: int, timeout: float = 120.0) -> None:
        for node in self.nodes:
            if node.proc is None or node.proc.poll() is not None:
                continue
            if not node.rpc.wait_for_height(h, timeout=timeout):
                raise TimeoutError(
                    f"{node.manifest.name} stuck below height {h} "
                    f"(at {node.rpc.height() if node.rpc.is_up() else '?'})"
                )

    def start_late_joiners(self, timeout: float = 120.0) -> None:
        """Start nodes with ``start_at > 0`` once the network has reached
        their join height; they must catch up via blocksync (reference:
        e2e 'startAt' nodes, runner/start.go)."""
        late = [n for n in self.nodes if n.manifest.start_at > 0]
        for node in sorted(late, key=lambda n: n.manifest.start_at):
            running = [
                n for n in self.nodes
                if n.proc is not None and n.proc.poll() is None
            ]
            assert running, "no running nodes for a late joiner to follow"
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                try:
                    if running[0].rpc.height() >= node.manifest.start_at:
                        break
                except Exception:
                    pass
                time.sleep(0.25)
            else:
                raise TimeoutError(
                    f"network never reached start_at="
                    f"{node.manifest.start_at} for {node.manifest.name}"
                )
            if node.manifest.state_sync:
                self._configure_state_sync(node, running)
            self.start_node(node)
            if not node.rpc.wait_for_height(
                node.manifest.start_at, timeout=timeout
            ):
                raise TimeoutError(
                    f"late joiner {node.manifest.name} failed to catch up"
                )

    def _configure_state_sync(self, node: RunningNode, running) -> None:
        """Fill the joiner's statesync config from the live network:
        >=2 RPC servers and a trusted header (reference: the operator
        copies trust_height/hash from a trusted RPC before boot)."""
        from cometbft_tpu.config import config as cfgmod

        src = running[0].rpc
        h = max(1, src.height() - 2)
        commit = src.commit(h)
        trust_hash = commit["signed_header"]["commit"]["block_id"]["hash"]
        cfg = cfgmod.load_config(node.home)
        cfg.statesync.enable = True
        cfg.statesync.rpc_servers = [
            n.rpc_laddr for n in (running * 2)[:2]
        ]
        cfg.statesync.trust_height = h
        cfg.statesync.trust_hash = trust_hash
        cfg.statesync.discovery_time_s = 3
        cfgmod.write_config(cfg)

    def load(self, duration_s: float) -> int:
        rpc = self.nodes[0].rpc
        return loadtime.generate(
            rpc,
            self.manifest.load_tx_rate,
            duration_s,
            self.manifest.load_tx_bytes,
        )

    def perturb(self) -> None:
        """Apply each node's manifest perturbations in sequence
        (reference: runner/perturb.go:47-91)."""
        for node in self.nodes:
            for p in node.manifest.perturb:
                if node.proc is None:
                    continue
                if p == "kill":
                    node.proc.send_signal(signal.SIGKILL)
                    node.proc.wait(timeout=10)
                    time.sleep(1.0)
                    self.start_node(node)
                    if not node.rpc.wait_for_height(1, timeout=60):
                        raise TimeoutError(
                            f"{node.manifest.name} dead after kill/restart"
                        )
                elif p == "pause":
                    node.proc.send_signal(signal.SIGSTOP)
                    time.sleep(3.0)
                    node.proc.send_signal(signal.SIGCONT)
                elif p == "restart":
                    node.proc.send_signal(signal.SIGTERM)
                    node.proc.wait(timeout=15)
                    self.start_node(node)
                    if not node.rpc.wait_for_height(1, timeout=60):
                        raise TimeoutError(
                            f"{node.manifest.name} dead after restart"
                        )
                elif p == "disconnect":
                    # no network namespace on localhost: approximate with a
                    # long pause (peer conns time out and must re-establish)
                    node.proc.send_signal(signal.SIGSTOP)
                    time.sleep(6.0)
                    node.proc.send_signal(signal.SIGCONT)
                elif p == "upgrade":
                    # binary-upgrade analog (reference perturb.go:88-131
                    # swaps docker images): restart the OS process as the
                    # manifest's upgrade_version; state must carry over
                    if node.upgraded:
                        raise RuntimeError(
                            f"{node.manifest.name}: can't upgrade twice"
                        )
                    new_v = self.manifest.upgrade_version
                    node.proc.send_signal(signal.SIGTERM)
                    node.proc.wait(timeout=15)
                    node.upgraded = True
                    node.env_extra["COMETBFT_TPU_SEMVER"] = new_v
                    self.start_node(node)
                    if not node.rpc.wait_for_height(1, timeout=60):
                        raise TimeoutError(
                            f"{node.manifest.name} dead after upgrade"
                        )
                    got = node.rpc.status()["node_info"]["version"]
                    if got != new_v:
                        raise RuntimeError(
                            f"{node.manifest.name} upgraded to {got!r}, "
                            f"wanted {new_v!r}"
                        )

    # -- invariants (reference: test/e2e/tests/*_test.go) -----------------

    def run_invariants(self) -> dict:
        """Black-box invariant checks over RPC; returns stats."""
        up = [n for n in self.nodes if n.proc and n.proc.poll() is None]
        assert up, "no nodes alive"
        heights = {n.manifest.name: n.rpc.height() for n in up}
        h = min(heights.values())
        assert h >= 2, f"chain did not progress: {heights}"

        # header/app-hash agreement at every sampled height
        ref_rpc = up[0].rpc
        earliest = {
            n.manifest.name: int(
                n.rpc.status()["sync_info"]["earliest_block_height"]
            )
            for n in up
            if n.manifest.state_sync
        }
        for sample in {2, max(2, h // 2), h}:
            ref_blk = ref_rpc.block(sample)
            want = ref_blk["block_id"]["hash"]
            want_app = ref_blk["block"]["header"]["app_hash"]
            for n in up[1:]:
                # heights below the snapshot are legitimately absent on a
                # state-synced node; anything else must compare
                if sample < earliest.get(n.manifest.name, 0):
                    continue
                blk = n.rpc.block(sample)
                assert blk["block_id"]["hash"] == want, (
                    f"fork at {sample}: {n.manifest.name}"
                )
                assert blk["block"]["header"]["app_hash"] == want_app

        # commit at h-1 carries +2/3 signatures
        commit = ref_rpc.commit(h - 1)
        vals = ref_rpc.validators(h - 1)["validators"]
        sigs = [
            s
            for s in commit["signed_header"]["commit"]["signatures"]
            if s.get("block_id_flag") == 2
        ]
        assert len(sigs) * 3 > 2 * len(vals) or len(sigs) == len(vals), (
            f"commit {h-1}: {len(sigs)}/{len(vals)} signatures"
        )

        # validator set matches genesis power
        assert len(vals) == len(self.manifest.validators)
        return {"heights": heights, "min_height": h}

    def benchmark(self, last_n: int = 20) -> dict:
        """Block-interval stats (reference: runner/benchmark.go:14-24)."""
        rpc = self.nodes[0].rpc
        h = rpc.height()
        lo = max(2, h - last_n)
        times = []
        for height in range(lo, h + 1):
            blk = rpc.block(height)["block"]
            times.append(loadtime._parse_block_time(blk["header"]["time"]))
        ivals = [b - a for a, b in zip(times, times[1:])]
        if not ivals:
            return {}
        return {
            "blocks": len(ivals),
            "interval_avg_s": sum(ivals) / len(ivals),
            "interval_min_s": min(ivals),
            "interval_max_s": max(ivals),
        }


def run(manifest_path: str, workdir: str, overrides: dict | None = None) -> dict:
    """Full pipeline; returns summary stats.  CLI: python -m e2e.runner
    <manifest.toml> [workdir].  ``overrides`` patches manifest fields
    (e.g. load_tx_rate for QA rate sweeps, scripts/qa_report.py)."""
    m = load_manifest(manifest_path)
    for k, v in (overrides or {}).items():
        setattr(m, k, v)
    net = Testnet(m, workdir)
    net.setup()
    summary = {}
    try:
        net.start()
        net.wait_height(2)
        net.start_late_joiners()
        sent = net.load(duration_s=max(2.0, m.wait_height * 0.5))
        net.perturb()
        net.wait_height(m.wait_height)
        summary["invariants"] = net.run_invariants()
        summary["benchmark"] = net.benchmark()
        rpc = net.nodes[0].rpc
        rep = loadtime.report(rpc, 2, rpc.height())
        summary["load"] = {
            "sent": sent,
            "report": str(rep) if rep else "no loadtime txs committed",
        }
        summary["loadtime"] = rep  # structured, for qa_report.py
    finally:
        net.stop()
    return summary


def main() -> int:
    if len(sys.argv) < 2:
        print("usage: python -m e2e.runner <manifest.toml> [workdir]")
        return 2
    manifest = sys.argv[1]
    workdir = (
        sys.argv[2]
        if len(sys.argv) > 2
        else os.path.join("/tmp", f"e2e-{int(time.time())}")
    )
    os.makedirs(workdir, exist_ok=True)
    summary = run(manifest, workdir)
    print(json.dumps(summary, indent=2, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
