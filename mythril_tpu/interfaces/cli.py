"""The myth command-line interface (reference: mythril/interfaces/cli.py).

Commands: analyze (a), disassemble (d), pro (p, MythX cloud submission),
list-detectors, read-storage, leveldb-search, function-to-hash,
hash-to-address, truffle, version, help.
"""

import argparse
import json
import logging
import os
import sys
from typing import List, Optional

import mythril_tpu
from mythril_tpu.analysis.module.loader import ModuleLoader
from mythril_tpu.exceptions import (
    CriticalError,
    DetectorNotFoundError,
    LoaderError,
)
from mythril_tpu.mythril import (
    MythrilAnalyzer,
    MythrilConfig,
    MythrilDisassembler,
)
from mythril_tpu.plugin.loader import MythrilPluginLoader
from mythril_tpu.support.crypto import keccak256

log = logging.getLogger(__name__)

ANALYZE_LIST = ("analyze", "a")
DISASSEMBLE_LIST = ("disassemble", "d")

COMMAND_LIST = (
    ANALYZE_LIST
    + DISASSEMBLE_LIST
    + (
        "pro",
        "serve",
        "worker",
        "top",
        "watch",
        "list-detectors",
        "read-storage",
        "leveldb-search",
        "function-to-hash",
        "hash-to-address",
        "version",
        "truffle",
        "help",
    )
)


def exit_with_error(format_: str, message: str) -> None:
    if format_ == "text" or format_ == "markdown":
        log.error(message)
        if not log.isEnabledFor(logging.ERROR):
            # below -v 2 the logger swallows the message; a silent
            # exit-with-no-output would look like a successful run
            print(message, file=sys.stderr)
    elif format_ == "json":
        print(json.dumps({"success": False, "error": str(message), "issues": []}))
    else:
        print(
            json.dumps(
                [
                    {
                        "issues": [],
                        "sourceType": "",
                        "sourceFormat": "",
                        "sourceList": [],
                        "meta": {
                            "logs": [
                                {
                                    "level": "error",
                                    "hidden": True,
                                    "error": message,
                                }
                            ]
                        },
                    }
                ]
            )
        )
    sys.exit()


def get_runtime_input_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "-a",
        "--address",
        help="pull contract from the blockchain",
        metavar="CONTRACT_ADDRESS",
    )
    parser.add_argument(
        "--bin-runtime",
        action="store_true",
        help="Only when -c or -f is used. Consider the input bytecode as "
        "binary runtime code, default being the contract creation bytecode.",
    )
    return parser


def get_creation_input_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "-c",
        "--code",
        help='hex-encoded bytecode string ("6060604052...")',
        metavar="BYTECODE",
    )
    parser.add_argument(
        "-f",
        "--codefile",
        help="file containing hex-encoded bytecode string",
        metavar="BYTECODEFILE",
        type=argparse.FileType("r"),
    )
    return parser


def get_output_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "-o",
        "--outform",
        choices=["text", "markdown", "json", "jsonv2"],
        default="text",
        help="report output format",
        metavar="<text/markdown/json/jsonv2>",
    )
    parser.add_argument(
        "--verbose-report",
        action="store_true",
        help="Include debugging information in report",
    )
    return parser


def get_rpc_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument(
        "--rpc",
        help="custom RPC settings",
        metavar="HOST:PORT / ganache / infura-[network_name]",
    )
    parser.add_argument(
        "--rpctls", type=bool, default=False, help="RPC connection over TLS"
    )
    return parser


def get_utilities_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--solc-json", help="Json for the optional 'settings' parameter of solc's standard-json input")
    parser.add_argument(
        "--solv",
        help="specify solidity compiler version.",
        metavar="SOLV",
    )
    parser.add_argument(
        "-v",
        type=int,
        help="log level (0-5)",
        metavar="LOG_LEVEL",
        default=2,
        dest="verbosity",
    )
    return parser


def create_analyzer_parser(analyzer_parser: argparse.ArgumentParser) -> None:
    analyzer_parser.add_argument(
        "solidity_files", nargs="*", help="Inputs file name and contract name"
    )
    commands = analyzer_parser.add_argument_group("commands")
    commands.add_argument("-g", "--graph", help="generate a control flow graph", metavar="OUTPUT_FILE")
    commands.add_argument(
        "-j",
        "--statespace-json",
        help="dumps the statespace json",
        metavar="OUTPUT_FILE",
    )
    options = analyzer_parser.add_argument_group("options")
    options.add_argument(
        "-m",
        "--modules",
        help="Comma-separated list of security analysis modules",
        metavar="MODULES",
    )
    options.add_argument(
        "--max-depth",
        type=int,
        default=128,
        help="Maximum recursion depth for symbolic execution",
    )
    options.add_argument(
        "--call-depth-limit",
        type=int,
        default=3,
        help="Maximum call depth limit for symbolic execution",
    )
    options.add_argument(
        "--strategy",
        choices=["dfs", "bfs", "naive-random", "weighted-random"],
        default="bfs",
        help="Symbolic execution strategy",
    )
    options.add_argument(
        "-b",
        "--loop-bound",
        type=int,
        default=3,
        help="Bound loops at n iterations",
        metavar="N",
    )
    options.add_argument(
        "-t",
        "--transaction-count",
        type=int,
        default=2,
        help="Maximum number of transactions issued by laser",
    )
    options.add_argument(
        "--execution-timeout",
        type=int,
        default=86400,
        help="The amount of seconds to spend on symbolic execution",
    )
    options.add_argument(
        "--solver-timeout",
        type=int,
        default=10000,
        help="The maximum amount of time (in milli seconds) the solver "
        "spends for queries from analysis modules",
    )
    options.add_argument(
        "--create-timeout",
        type=int,
        default=10,
        help="The amount of seconds to spend on the initial contract creation",
    )
    options.add_argument(
        "--parallel-solving",
        action="store_true",
        help="Enable solving parallelization",
    )
    options.add_argument(
        "--no-batched-solving",
        action="store_true",
        help="Disable the batched frontier feasibility path (per-state "
        "solving only, as in the reference)",
    )
    options.add_argument(
        "--device-force-dispatch",
        action="store_true",
        help="Dispatch frontiers to the accelerator whenever the size "
        "gates allow, bypassing the adaptive profit gate (capability/"
        "benchmark runs)",
    )
    options.add_argument(
        "--lockstep-dispatch",
        action="store_true",
        help="Pre-split transaction seeds by function selector via the "
        "SoA-validated dispatcher plan (now the default; kept for "
        "script compatibility)",
    )
    options.add_argument(
        "--no-lockstep-dispatch",
        action="store_true",
        help="Disable the dispatcher pre-split: every transaction seed "
        "executes the full dispatcher prefix serially (the pre-split "
        "already auto-declines per contract on non-canonical "
        "dispatchers)",
    )
    options.add_argument(
        "--no-async-dispatch",
        action="store_true",
        help="Disable the asynchronous device prefetch (profit-gate-"
        "declined frontiers launching on the accelerator without "
        "blocking; see ops/async_dispatch.py)",
    )
    options.add_argument(
        "--workers",
        type=int,
        default=None,
        metavar="N",
        help="Shard the transaction-boundary frontier across N fleet "
        "worker processes (coordinator/worker leases with heartbeat "
        "failure detection, journal re-lease, and epoch-fenced "
        "knowledge gossip — docs/scaling.md).  0 forces the exact "
        "single-process path; default defers to "
        "MYTHRIL_TPU_FLEET_WORKERS (kill switch MYTHRIL_TPU_FLEET=0)",
    )
    options.add_argument(
        "--checkpoint-dir",
        help="Journal the analysis (frontier, findings, solver memo "
        "channels) into this directory so a preempted run can be "
        "resumed; cadence via MYTHRIL_TPU_CHECKPOINT_PERIOD "
        "(seconds, default 30)",
        metavar="DIR",
    )
    options.add_argument(
        "--resume",
        dest="resume_dir",
        help="Resume a preempted analysis from the journal in DIR "
        "(implies --checkpoint-dir DIR); findings are identical to an "
        "uninterrupted run",
        metavar="DIR",
    )
    options.add_argument(
        "--trace-out",
        help="Write a Chrome/Perfetto trace_event JSON timeline of the "
        "analysis to FILE: hierarchical spans across CLI -> analyzer -> "
        "svm rounds -> dispatch -> ladder rounds -> H2D uploads -> the "
        "CDCL tail, with watchdog trips / fault injections / demotions "
        "/ checkpoint writes as instant events (open at "
        "https://ui.perfetto.dev; kill switch MYTHRIL_TPU_TRACE=0)",
        metavar="FILE",
    )
    options.add_argument(
        "--metrics-out",
        help="Dump the unified metrics registry (resilience, dispatch, "
        "async-prefetch and trace counters) in Prometheus text format "
        "to FILE when the analysis ends",
        metavar="FILE",
    )
    options.add_argument(
        "--lane-ledger-out",
        help="Write the per-lane attribution ledger to FILE as JSON "
        "(schema mythril-tpu-lane-ledger/1): every dispatch lane's "
        "origin, tier transitions and verdict, plus per-tier/"
        "per-contract aggregates; validate with scripts/trace_lint.py "
        "(kill switch MYTHRIL_TPU_LEDGER=0)",
        metavar="FILE",
    )
    options.add_argument(
        "--proof-log",
        action="store_true",
        help="Record a DRAT-style proof stream on the native solver and "
        "certify every UNSAT verdict with the independent checker "
        "before reporting (wrong-UNSAT defense; adds memory and time)",
    )
    options.add_argument(
        "--no-onchain-data",
        action="store_true",
        help="Don't attempt to retrieve contract code, variables and balances from the blockchain",
    )
    options.add_argument(
        "--sparse-pruning",
        action="store_true",
        help="Checks for reachability after the end of tx. Recommended for "
        "short execution timeouts < 1 minute",
    )
    options.add_argument(
        "--unconstrained-storage",
        action="store_true",
        help="Default storage value is symbolic, turns off the on-chain "
        "loading of storage",
    )
    options.add_argument(
        "--phrack", action="store_true", help="Phrack-style call graph"
    )
    options.add_argument(
        "--enable-physics",
        action="store_true",
        help="enable graph physics simulation",
    )
    options.add_argument(
        "-q",
        "--query-signature",
        action="store_true",
        help="Lookup function signatures through www.4byte.directory",
    )
    options.add_argument(
        "--enable-iprof",
        action="store_true",
        help="enable the instruction profiler",
    )
    options.add_argument(
        "--disable-dependency-pruning",
        action="store_true",
        help="Deactivate dependency-based pruning",
    )
    options.add_argument(
        "--enable-coverage-strategy",
        action="store_true",
        help="enable coverage based search strategy",
    )
    options.add_argument(
        "--custom-modules-directory",
        help="designates a separate directory to search for custom "
        "analysis modules",
        metavar="CUSTOM_MODULES_DIRECTORY",
    )
    options.add_argument(
        "--attacker-address",
        help="Designates a specific attacker address to use during analysis",
        metavar="ATTACKER_ADDRESS",
    )
    options.add_argument(
        "--persist-dir",
        help="directory for the persistent knowledge store: solver "
        "memos, autopilot EWMAs and finished reports survive the "
        "process and warm-start later runs (env: "
        "MYTHRIL_TPU_PERSIST_DIR; kill switch MYTHRIL_TPU_PERSIST=0)",
        metavar="DIR",
    )
    options.add_argument(
        "--creator-address",
        help="Designates a specific creator address to use during analysis",
        metavar="CREATOR_ADDRESS",
    )


def create_serve_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--host",
        default="127.0.0.1",
        help="interface to bind (default 127.0.0.1)",
    )
    parser.add_argument(
        "-p",
        "--port",
        type=int,
        default=8551,
        help="TCP port to listen on (0 = ephemeral)",
    )
    parser.add_argument(
        "--trace-out",
        help="Write the server's Perfetto span timeline here on drain "
        "(every request gets a serve.request span tree)",
        metavar="FILE",
    )
    parser.add_argument(
        "--metrics-out",
        help="Also dump the metrics registry to FILE on drain (the "
        "live view is GET /metrics)",
        metavar="FILE",
    )
    parser.add_argument(
        "--lane-ledger-out",
        help="Also dump the per-lane attribution ledger to FILE on "
        "drain (the live view is GET /debug/lanes)",
        metavar="FILE",
    )
    parser.add_argument(
        "--fleet-listen",
        help="HOST:PORT the serving fabric's coordinator listens on "
        "for `myth worker --connect` attach (non-loopback requires "
        "--secret-file; env: MYTHRIL_TPU_FLEET_LISTEN)",
        metavar="HOST:PORT",
    )
    parser.add_argument(
        "--secret-file",
        help="shared-secret file authenticating fabric workers "
        "(env: MYTHRIL_TPU_FLEET_SECRET_FILE)",
        metavar="FILE",
    )
    parser.add_argument(
        "--persist-dir",
        help="directory for the persistent knowledge store: loaded at "
        "startup, flushed on drain — restarts answer seen contracts "
        "warm (env: MYTHRIL_TPU_PERSIST_DIR)",
        metavar="DIR",
    )


def create_worker_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--connect",
        required=True,
        help="HOST:PORT of the coordinator's fleet listener "
        "(`myth serve --fleet-listen` or a --workers N coordinator)",
        metavar="HOST:PORT",
    )
    parser.add_argument(
        "--secret-file",
        help="shared-secret file for the fabric handshake (env: "
        "MYTHRIL_TPU_FLEET_SECRET_FILE; required when the "
        "coordinator listens on a routable interface)",
        metavar="FILE",
    )
    parser.add_argument(
        "--id",
        help="worker id announced in the hello (default "
        "HOSTNAME-PID)",
        metavar="ID",
    )
    parser.add_argument(
        "--persist-dir",
        help="directory for the persistent knowledge store shared "
        "with (or private to) this seat (env: MYTHRIL_TPU_PERSIST_DIR)",
        metavar="DIR",
    )
    parser.add_argument(
        "--reconnect",
        type=int,
        default=None,
        help="redial attempts after a lost coordinator connection "
        "(default MYTHRIL_TPU_FLEET_RECONNECT or 5; 0 = exit on "
        "first disconnect)",
        metavar="N",
    )


def create_top_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--url",
        default="http://127.0.0.1:8551",
        help="base URL of a running `myth serve` daemon (or a fleet "
        "coordinator's MYTHRIL_TPU_FLEET_DEBUG_PORT listener)",
    )
    parser.add_argument(
        "--interval",
        type=float,
        default=2.0,
        help="refresh period in seconds",
    )
    parser.add_argument(
        "--once",
        action="store_true",
        help="render a single snapshot and exit (no screen clearing)",
    )


def create_watch_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--rpc",
        help="comma-separated RPC providers (URL or HOST[:PORT] each; "
        "env: MYTHRIL_TPU_RPC_PROVIDERS) the chain follower polls",
        metavar="SPEC",
    )
    parser.add_argument(
        "--serve",
        help="base URL of a running `myth serve` daemon to stream "
        "deployments into (default: an in-process engine)",
        metavar="URL",
    )
    parser.add_argument(
        "--from-block",
        type=int,
        default=None,
        help="backfill start height (env: "
        "MYTHRIL_TPU_WATCH_FROM_BLOCK; default 0)",
        metavar="N",
    )
    parser.add_argument(
        "--until-block",
        type=int,
        default=None,
        help="stop once the cursor reaches N and the backlog is "
        "empty (default: follow forever until drained)",
        metavar="N",
    )
    parser.add_argument(
        "--confirmations",
        type=int,
        default=None,
        help="confirmation-depth lag behind the head (env: "
        "MYTHRIL_TPU_WATCH_CONFIRMATIONS; default 2)",
        metavar="N",
    )
    parser.add_argument(
        "--poll-s",
        type=float,
        default=None,
        help="head poll period in seconds when caught up (env: "
        "MYTHRIL_TPU_WATCH_POLL_S; default 2.0)",
        metavar="S",
    )
    parser.add_argument(
        "--journal",
        help="fsynced cursor journal; with --resume a SIGKILLed "
        "watcher continues losing no block and re-analyzing nothing",
        metavar="FILE",
    )
    parser.add_argument(
        "--resume",
        action="store_true",
        help="replay --journal before following (cursor, seen "
        "digests, pending submissions)",
    )
    parser.add_argument(
        "--findings-out",
        help="JSONL findings sink: one row per submission outcome "
        "(analyzed / cached / duplicate / error)",
        metavar="FILE",
    )
    parser.add_argument(
        "--tx-count",
        type=int,
        default=2,
        help="transaction depth per analysis",
        metavar="N",
    )
    parser.add_argument(
        "--deadline-s",
        type=float,
        default=None,
        help="per-analysis wall-clock budget (default: the serve "
        "plane's default deadline)",
        metavar="S",
    )
    parser.add_argument(
        "--trace-out",
        help="Write the watcher's Perfetto span timeline here on exit "
        "(watch.poll/block/extract/submit spans)",
        metavar="FILE",
    )
    parser.add_argument(
        "--metrics-out",
        help="Dump the metrics registry (mythril_tpu_watch_*) to FILE "
        "on exit",
        metavar="FILE",
    )
    parser.add_argument(
        "--persist-dir",
        help="directory for the persistent knowledge store: the "
        "report cache that makes re-submissions answer cached "
        "(env: MYTHRIL_TPU_PERSIST_DIR)",
        metavar="DIR",
    )


def create_disassemble_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "solidity_files",
        nargs="*",
        help="Inputs file name and contract name. "
        "usage: file:contractName",
    )


def create_read_storage_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "storage_slots",
        help="read state variables from storage index",
        metavar="INDEX,NUM_SLOTS,[array] / mapping,INDEX,[KEY1, KEY2...]",
    )
    parser.add_argument(
        "address", help="contract address", metavar="CONTRACT_ADDRESS"
    )


def create_func_to_hash_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "func_name", help="calculate function signature hash", metavar="SIGNATURE"
    )


def create_hash_to_addr_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "hash",
        help="contract code hash (0x + 64 hex chars) to resolve to an "
        "address",
        metavar="HASH",
    )
    parser.add_argument(
        "--leveldb-dir",
        help="specify leveldb directory for search or direct access "
        "operations",
        metavar="LEVELDB_PATH",
    )


def create_leveldb_parser(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "search", help="search expression", metavar="EXPRESSION"
    )
    parser.add_argument(
        "--leveldb-dir",
        help="specify leveldb directory for search or direct access "
        "operations",
        metavar="LEVELDB_PATH",
    )


def main() -> None:
    """The main CLI interface entry point."""
    program_name = "myth"
    parser = argparse.ArgumentParser(
        prog=program_name,
        description="Security analysis of Ethereum smart contracts "
        "(TPU-native build)",
    )
    parser.add_argument(
        "--epic", action="store_true", help=argparse.SUPPRESS
    )
    subparsers = parser.add_subparsers(dest="command", help="Commands")

    rpc_parser = get_rpc_parser()
    utilities_parser = get_utilities_parser()
    creation_input_parser = get_creation_input_parser()
    runtime_input_parser = get_runtime_input_parser()
    output_parser = get_output_parser()

    analyzer_parser = subparsers.add_parser(
        ANALYZE_LIST[0],
        help="Triggers the analysis of the smart contract",
        parents=[
            rpc_parser, utilities_parser, creation_input_parser,
            runtime_input_parser, output_parser,
        ],
        aliases=ANALYZE_LIST[1:],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_analyzer_parser(analyzer_parser)

    disassemble_parser = subparsers.add_parser(
        DISASSEMBLE_LIST[0],
        help="Disassembles the smart contract",
        aliases=DISASSEMBLE_LIST[1:],
        parents=[
            rpc_parser, utilities_parser, creation_input_parser,
            runtime_input_parser,
        ],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_disassemble_parser(disassemble_parser)

    subparsers.add_parser(
        "list-detectors",
        parents=[output_parser],
        help="Lists available detection modules",
    )
    read_storage_parser = subparsers.add_parser(
        "read-storage",
        help="Retrieves storage slots from a given address through rpc",
        parents=[rpc_parser],
    )
    create_read_storage_parser(read_storage_parser)
    func_to_hash_parser = subparsers.add_parser(
        "function-to-hash", help="Returns the hash of a function signature"
    )
    create_func_to_hash_parser(func_to_hash_parser)
    hash_to_addr_parser = subparsers.add_parser(
        "hash-to-address",
        help="Returns the address for a contract code hash (LevelDB)",
    )
    create_hash_to_addr_parser(hash_to_addr_parser)
    serve_parser = subparsers.add_parser(
        "serve",
        help="Run the persistent analysis daemon: bounded admission, "
        "per-request deadline budgets, request isolation, live "
        "/healthz /readyz /metrics (docs/serving.md)",
        parents=[utilities_parser],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_serve_parser(serve_parser)
    worker_parser = subparsers.add_parser(
        "worker",
        help="Attach this machine to a serving fabric as a worker "
        "seat: connect to a coordinator's --fleet-listen endpoint, "
        "authenticate with the shared secret, run leases until "
        "drained (docs/scaling.md)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_worker_parser(worker_parser)
    top_parser = subparsers.add_parser(
        "top",
        help="Live one-screen status of a running serve daemon or "
        "fleet coordinator (polls /debug/requests + /debug/lanes; "
        "docs/observability.md)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_top_parser(top_parser)
    watch_parser = subparsers.add_parser(
        "watch",
        help="Follow new blocks on a live chain and stream every "
        "newly deployed contract through the serve fabric: "
        "reorg-tolerant cursor, clone/proxy dedup, backpressure "
        "backlog (docs/watch.md)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_watch_parser(watch_parser)
    subparsers.add_parser("version", parents=[output_parser], help="Outputs the version")
    pro_parser = subparsers.add_parser(
        "pro",
        help="Submits the contract to a cloud analysis endpoint "
        "(requires MYTHX_API_URL)",
        parents=[
            rpc_parser, utilities_parser, creation_input_parser,
            runtime_input_parser, output_parser,
        ],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_analyzer_parser(pro_parser)
    truffle_parser = subparsers.add_parser(
        "truffle",
        help="Analyze a truffle project (run from the project directory)",
        parents=[utilities_parser, output_parser],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    create_analyzer_parser(truffle_parser)
    leveldb_search_parser = subparsers.add_parser(
        "leveldb-search", help="Searches the code fragment in local leveldb"
    )
    create_leveldb_parser(leveldb_search_parser)
    subparsers.add_parser("help", add_help=False)

    args = parser.parse_args()
    parse_args_and_execute(parser=parser, args=args)


def set_config(args: argparse.Namespace) -> MythrilConfig:
    config = MythrilConfig()
    if getattr(args, "rpc", None):
        config.set_api_rpc(rpc=args.rpc, rpctls=args.rpctls)
    elif not getattr(args, "no_onchain_data", True):
        config.set_api_from_config_path()
    return config


def load_code(disassembler: MythrilDisassembler, args: argparse.Namespace):
    address = None
    if args.code is not None:
        address, _ = disassembler.load_from_bytecode(
            args.code, args.bin_runtime, address
        )
    elif args.codefile:
        bytecode = "".join([l.strip() for l in args.codefile if len(l.strip()) > 0])
        address, _ = disassembler.load_from_bytecode(
            bytecode, args.bin_runtime, address
        )
    elif args.address:
        address, _ = disassembler.load_from_address(args.address)
    elif args.solidity_files:
        address, _ = disassembler.load_from_solidity(args.solidity_files)
    else:
        exit_with_error(
            getattr(args, "outform", "text"),
            "No input bytecode. Please provide EVM code via -c BYTECODE, "
            "-a ADDRESS, -f BYTECODE_FILE or <SOLIDITY_FILE>",
        )
    return address


def _build_analyzer(
    disassembler: MythrilDisassembler,
    address,
    args: argparse.Namespace,
    use_onchain_data: bool,
) -> MythrilAnalyzer:
    """One construction point for MythrilAnalyzer from CLI flags
    (shared by analyze and truffle so new flags can't drift apart)."""
    return MythrilAnalyzer(
        batched_solving=not args.no_batched_solving,
        device_force_dispatch=args.device_force_dispatch,
        lockstep_dispatch=not args.no_lockstep_dispatch,
        proof_log=args.proof_log,
        async_dispatch=not args.no_async_dispatch,
        checkpoint_dir=getattr(args, "checkpoint_dir", None),
        resume_from=getattr(args, "resume_dir", None),
        fleet_workers=getattr(args, "workers", None),
        strategy=args.strategy,
        disassembler=disassembler,
        address=address,
        max_depth=args.max_depth,
        execution_timeout=args.execution_timeout,
        loop_bound=args.loop_bound,
        create_timeout=args.create_timeout,
        enable_iprof=args.enable_iprof,
        disable_dependency_pruning=args.disable_dependency_pruning,
        use_onchain_data=use_onchain_data,
        solver_timeout=args.solver_timeout,
        parallel_solving=args.parallel_solving,
        custom_modules_directory=args.custom_modules_directory or "",
        sparse_pruning=args.sparse_pruning,
        unconstrained_storage=args.unconstrained_storage,
        call_depth_limit=args.call_depth_limit,
        enable_coverage_strategy=args.enable_coverage_strategy,
    )


def _fire_and_print(analyzer: MythrilAnalyzer, args: argparse.Namespace) -> None:
    report = analyzer.fire_lasers(
        modules=[m.strip() for m in args.modules.strip().split(",")]
        if args.modules
        else None,
        transaction_count=args.transaction_count,
    )
    renderers = {
        "json": report.as_json,
        "jsonv2": report.as_swc_standard_format,
        "text": report.as_text,
        "markdown": report.as_markdown,
    }
    print(renderers[getattr(args, "outform", "text")]())


def execute_truffle(args: argparse.Namespace) -> None:
    """Analyze every compiled artifact of a truffle project: run from
    the project root after ``truffle compile``; each
    ``build/contracts/*.json`` artifact's deployed bytecode is analyzed
    like ``analyze --bin-runtime``.  (The reference registers this
    command but ships no handler for it — cli.py:268 registers the
    subparser, execute_command has no truffle branch.)"""
    outform = getattr(args, "outform", "text")
    build_dir = os.path.join(os.getcwd(), "build", "contracts")
    if not os.path.isdir(build_dir):
        exit_with_error(
            outform,
            "No build/contracts directory here. Run `truffle compile` in "
            "the project first, then `myth truffle` from the project root.",
        )

    disassembler = MythrilDisassembler(eth=None)
    address = None
    for filename in sorted(os.listdir(build_dir)):
        if not filename.endswith(".json"):
            continue
        with open(os.path.join(build_dir, filename)) as fh:
            try:
                artifact = json.load(fh)
            except json.JSONDecodeError:
                continue
        runtime = (artifact.get("deployedBytecode") or "").strip()
        if runtime in ("", "0x"):
            continue  # interfaces / abstract contracts have no code
        loaded_address, _ = disassembler.load_from_bytecode(
            runtime, bin_runtime=True
        )
        address = address or loaded_address
        disassembler.contracts[-1].name = artifact.get(
            "contractName", filename[:-5]
        )

    if not disassembler.contracts:
        exit_with_error(
            outform, "No deployable contracts found in build/contracts."
        )

    _fire_and_print(
        _build_analyzer(disassembler, address, args, use_onchain_data=False),
        args,
    )


def execute_command(
    disassembler: MythrilDisassembler,
    address: str,
    parser: argparse.ArgumentParser,
    args: argparse.Namespace,
) -> None:
    if args.command == "pro":
        from mythril_tpu import mythx

        try:
            report = mythx.analyze(disassembler.contracts)
        except mythx.MythXApiError as e:
            raise CriticalError(str(e)) from e
        print(
            report.as_json() if args.outform == "json" else report.as_text()
        )
        return

    if args.command in DISASSEMBLE_LIST:
        if disassembler.contracts[0].code:
            print("Runtime Disassembly: \n" + disassembler.contracts[0].get_easm())
        if disassembler.contracts[0].creation_code:
            print(
                "Disassembly: \n"
                + disassembler.contracts[0].get_creation_easm()
            )
        return

    if args.command in ANALYZE_LIST:
        analyzer = _build_analyzer(
            disassembler, address, args,
            use_onchain_data=not args.no_onchain_data,
        )

        if not disassembler.contracts:
            exit_with_error(
                args.outform, "input files do not contain any valid contracts"
            )

        if args.graph:
            html = analyzer.graph_html(
                contract=analyzer.contracts[0],
                enable_physics=args.enable_physics,
                phrackify=args.phrack,
                transaction_count=args.transaction_count,
            )
            try:
                with open(args.graph, "w") as f:
                    f.write(html)
            except Exception as e:
                exit_with_error(args.outform, f"Error saving graph: {e}")
            return
        if args.statespace_json:
            if not analyzer.contracts:
                exit_with_error(
                    args.outform, "input files do not contain any valid contracts"
                )
            statespace = analyzer.dump_statespace(contract=analyzer.contracts[0])
            try:
                with open(args.statespace_json, "w") as f:
                    json.dump(statespace, f)
            except Exception as e:
                exit_with_error(args.outform, f"Error saving json: {e}")
            return

        try:
            _fire_and_print(analyzer, args)
        except DetectorNotFoundError as e:
            exit_with_error(args.outform, format(e))
        except LoaderError as e:
            # typed wild-input failure: one machine-readable line on
            # stderr, exit 2 (before CriticalError — its parent — whose
            # handler exits 0)
            print(e.to_line(), file=sys.stderr)
            sys.exit(2)
        except CriticalError as e:
            exit_with_error(
                args.outform, "Analysis error encountered: " + format(e)
            )
        return


def parse_args_and_execute(parser: argparse.ArgumentParser, args: argparse.Namespace) -> None:
    if args.command not in ANALYZE_LIST and args.command != "truffle":
        _execute(parser, args)
        return
    from mythril_tpu.observability import analysis, finalize_outputs

    # the analysis record opens before the contract is loaded and closes
    # once the report is printed, so its wall is the command's; the
    # --trace-out / --metrics-out artifacts, which carry the record,
    # are written after it closes, also when printing or the analysis
    # failed
    try:
        with analysis("cli.analyze"):
            _execute(parser, args)
    finally:
        finalize_outputs()


def _execute(parser: argparse.ArgumentParser, args: argparse.Namespace) -> None:
    if args.epic:
        import shlex
        import subprocess

        path = os.path.dirname(os.path.realpath(__file__))
        sys.argv.remove("--epic")
        # re-run ourselves piped through the rainbow pager; arguments are
        # quoted so paths with spaces/metacharacters survive the shell,
        # and the re-exec goes through the interpreter explicitly (when
        # invoked as `python3 myth ...`, argv[0] alone is not on PATH).
        # A PATH-installed console script arrives as a bare name in
        # argv[0] — resolve it first (the stub is a python script, so
        # interpreter + resolved path still works).
        import shutil

        interpreter = shlex.quote(sys.executable or "python3")
        argv0 = sys.argv[0]
        if not os.path.exists(argv0):
            argv0 = shutil.which(argv0) or argv0
        command = (
            interpreter
            + " "
            + " ".join(shlex.quote(arg) for arg in [argv0] + sys.argv[1:])
            + " | "
            + interpreter
            + " "
            + shlex.quote(os.path.join(path, "epic.py"))
        )
        sys.exit(subprocess.call(command, shell=True))

    if args.command not in COMMAND_LIST or args.command is None:
        parser.print_help()
        sys.exit()

    if args.command == "version":
        if args.outform == "json":
            print(json.dumps({"version_str": mythril_tpu.__version__}))
        else:
            print(f"Mythril-TPU version {mythril_tpu.__version__}")
        sys.exit()

    if args.command == "help":
        parser.print_help()
        sys.exit()

    # Logging
    log_levels = [
        logging.NOTSET, logging.CRITICAL, logging.ERROR, logging.WARNING,
        logging.INFO, logging.DEBUG,
    ]
    level = log_levels[min(getattr(args, "verbosity", 2), 5)]
    logging.basicConfig(
        level=level, format="%(name)s [%(levelname)s]: %(message)s"
    )
    logging.getLogger("mythril_tpu").setLevel(level)

    if getattr(args, "enable_iprof", False) and getattr(args, "verbosity", 2) < 4:
        # parity with the reference (cli.py:552): profiler output goes
        # through the logger, so it is invisible below -v 4
        exit_with_error(
            getattr(args, "outform", "text"),
            "--enable-iprof must be used with -v LOG_LEVEL where LOG_LEVEL >= 4",
        )

    # numeric MYTHRIL_TPU_* knobs are validated here with the same
    # exit-2 contract as fault/serve specs: a typo'd value must die at
    # startup, never silently run a default mid-analysis
    # (support/env.py)
    from mythril_tpu.support.env import EnvSpecError, validate_env

    try:
        validate_env()
    except EnvSpecError as e:
        print(f"bad environment knob: {e}", file=sys.stderr)
        sys.exit(2)

    if getattr(args, "persist_dir", None):
        # --persist-dir wins over the env knob, and travels through the
        # environment so spawned fleet workers inherit the store
        os.environ["MYTHRIL_TPU_PERSIST_DIR"] = args.persist_dir

    if os.environ.get("MYTHRIL_TPU_FAULT") or os.environ.get(
        "MYTHRIL_TPU_KILL_AT"
    ):
        # chaos specs must fail loudly HERE: a typo'd injection point
        # that parsed lazily mid-analysis used to be swallowed by the
        # batch path's broad except and pass the run vacuously
        from mythril_tpu.resilience.faults import (
            FaultSpecError, get_fault_plane,
        )

        try:
            get_fault_plane()
        except FaultSpecError as e:
            # nonzero on purpose (exit_with_error exits 0): a chaos CI
            # gate keying on $? must see the schedule was rejected
            print(f"bad fault spec: {e}", file=sys.stderr)
            sys.exit(2)

    if args.command in ANALYZE_LIST or args.command in (
        "truffle", "serve", "watch",
    ):
        # graceful drain: SIGTERM/SIGINT walk the cooperative
        # cancellation checkpoints, land a final journal generation,
        # and ship a partial report (meta.resilience.partial) instead
        # of dying mid-dispatch; in serve mode the same flag drains the
        # daemon (admission closes, in-flight request finishes,
        # artifacts flush)
        from mythril_tpu.resilience.checkpoint import install_signal_handlers

        install_signal_handlers()
        # observability plane: --trace-out enables the span tracer,
        # --metrics-out requests a Prometheus dump at exit; both hook
        # the flight recorder's crash dump (docs/observability.md)
        from mythril_tpu.observability import configure_from_cli

        configure_from_cli(
            getattr(args, "trace_out", None),
            getattr(args, "metrics_out", None),
            getattr(args, "lane_ledger_out", None),
        )

    if args.command in ANALYZE_LIST or args.command in (
        "truffle", "serve", "watch",
    ):
        # the persistent compile cache must be in place before the
        # first jit of any command that compiles
        from mythril_tpu.ops import configure_jax

        configure_jax()

    if args.command == "serve":
        # serve-plane knobs are env-validated at startup: a typo'd
        # MYTHRIL_TPU_SERVE_* value must die loudly here (exit 2, the
        # FaultSpecError contract), never as an un-shed overload later
        from mythril_tpu.serve import ServeConfigError, run_server

        try:
            sys.exit(run_server(
                host=args.host, port=args.port,
                fleet_listen=args.fleet_listen,
                secret_file=args.secret_file,
            ))
        except ServeConfigError as e:
            print(f"bad serve config: {e}", file=sys.stderr)
            sys.exit(2)
        except OSError as e:
            print(f"cannot bind {args.host}:{args.port}: {e}",
                  file=sys.stderr)
            sys.exit(1)

    if args.command == "watch":
        # live-chain ingestion (mythril_tpu/watch): typed provider
        # exhaustion and serve-config typos die as one-line structured
        # exit-2s, the same contract as the serve/sweep commands
        from mythril_tpu.exceptions import ProviderExhaustedError
        from mythril_tpu.serve import ServeConfigError
        from mythril_tpu.watch import run_watch

        try:
            sys.exit(run_watch(args))
        except ProviderExhaustedError as e:
            print(json.dumps({"error": {
                "code": e.code, "message": str(e),
            }}), file=sys.stderr)
            sys.exit(2)
        except ServeConfigError as e:
            print(f"bad serve config: {e}", file=sys.stderr)
            sys.exit(2)

    if args.command == "worker":
        # a worker seat must never recursively spawn its own fleet
        os.environ["MYTHRIL_TPU_FLEET_ROLE"] = "worker"
        import socket as socket_mod

        from mythril_tpu.parallel.fleet import worker_main

        worker_id = args.id or (
            f"{socket_mod.gethostname()}-{os.getpid()}"
        )
        worker_argv = ["--worker", "--connect", args.connect,
                       "--id", worker_id]
        if args.secret_file:
            worker_argv += ["--secret-file", args.secret_file]
        reconnect = args.reconnect
        if reconnect is None and not os.environ.get(
            "MYTHRIL_TPU_FLEET_RECONNECT"
        ):
            reconnect = 5  # survive a coordinator restart by default
        if reconnect is not None:
            worker_argv += ["--reconnect", str(reconnect)]
        sys.exit(worker_main(worker_argv))

    if args.command == "top":
        from mythril_tpu.interfaces.top import run_top

        sys.exit(run_top(args.url, interval_s=args.interval,
                         once=args.once))

    if args.command == "function-to-hash":
        print(MythrilDisassembler.hash_for_function_signature(args.func_name))
        sys.exit()

    if args.command in ("hash-to-address", "leveldb-search"):
        from mythril_tpu.mythril.mythril_leveldb import MythrilLevelDB

        config = MythrilConfig()
        leveldb_dir = (
            getattr(args, "leveldb_dir", None) or config.leveldb_dir
        )
        try:
            config.set_api_leveldb(leveldb_dir)
        except Exception as e:
            exit_with_error(
                "text", f"Cannot open LevelDB at {leveldb_dir}: {e}"
            )
        searcher = MythrilLevelDB(config.eth_db)
        try:
            if args.command == "leveldb-search":
                searcher.search_db(args.search)
            else:
                searcher.contract_hash_to_address(args.hash)
        except CriticalError as e:
            exit_with_error("text", str(e))
        sys.exit()

    if args.command == "list-detectors":
        modules = []
        for module in ModuleLoader().get_detection_modules():
            modules.append({"classname": type(module).__name__, "title": module.name})
        if args.outform == "json":
            print(json.dumps(modules))
        else:
            for module_data in modules:
                print(f"{module_data['classname']}: {module_data['title']}")
        sys.exit()

    if args.command == "pro":
        # cheap precheck before any compile/load work; the actual
        # submission happens in execute_command via the shared
        # disassembler/load_code path
        from mythril_tpu import mythx

        if mythx.api_url() is None:
            exit_with_error(
                getattr(args, "outform", "text"),
                "The 'pro' command submits contracts to a cloud analysis "
                "endpoint; set MYTHX_API_URL to use it (this environment "
                "has no network egress by default).",
            )

    if args.command == "truffle":
        execute_truffle(args)
        sys.exit()

    # load mythril-level plugins (entry-point discovery)
    MythrilPluginLoader()

    if args.command == "read-storage":
        config = set_config(args)
        if config.eth is None:
            config.set_api_rpc(args.rpc or "localhost:8545", args.rpctls)
        disassembler = MythrilDisassembler(eth=config.eth)
        storage = disassembler.get_state_variable_from_storage(
            address=args.address,
            params=[a.strip() for a in args.storage_slots.strip().split(",")],
        )
        print(storage)
        return

    # analyze / disassemble need loaded code
    if getattr(args, "attacker_address", None):
        from mythril_tpu.laser.ethereum.transaction.symbolic import ACTORS

        try:
            ACTORS["ATTACKER"] = int(args.attacker_address, 16)
        except ValueError:
            exit_with_error(args.outform, "Attacker address is invalid")
    if getattr(args, "creator_address", None):
        from mythril_tpu.laser.ethereum.transaction.symbolic import ACTORS

        try:
            ACTORS["CREATOR"] = int(args.creator_address, 16)
        except ValueError:
            exit_with_error(args.outform, "Creator address is invalid")

    config = set_config(args)
    solv = getattr(args, "solv", None)
    query_signature = getattr(args, "query_signature", False)
    solc_json = getattr(args, "solc_json", None)
    try:
        disassembler = MythrilDisassembler(
            eth=config.eth,
            solc_version=solv,
            solc_settings_json=solc_json,
            enable_online_lookup=query_signature,
        )
        address = load_code(disassembler, args)
        execute_command(
            disassembler=disassembler, address=address, parser=parser, args=args
        )
    except LoaderError as le:
        # bad checksum / empty code / provider exhaustion: a one-line
        # structured error a sweep driver can parse, and — unlike
        # exit_with_error, which exits 0 — a nonzero exit so CI can
        # tell "input rejected" from "analysis clean".  Must precede
        # the CriticalError handler (LoaderError subclasses it).
        print(le.to_line(), file=sys.stderr)
        sys.exit(2)
    except CriticalError as ce:
        exit_with_error(getattr(args, "outform", "text"), str(ce))
    except Exception:
        import traceback

        exit_with_error(getattr(args, "outform", "text"), traceback.format_exc())


if __name__ == "__main__":
    main()
