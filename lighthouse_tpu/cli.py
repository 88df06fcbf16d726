"""CLI multiplexer: beacon node, validator client, accounts, dev tools.

Role of the reference's `lighthouse` binary (lighthouse/src/main.rs:34
subcommand multiplexer), account_manager, database_manager, and lcli (dev
Swiss-army tools: transition-blocks, skip-slots, new-testnet, ssz parsing).

    python -m lighthouse_tpu bn --network minimal --validators 32 --slots 16
    python -m lighthouse_tpu vc ...
    python -m lighthouse_tpu account new --password ... --out key.json
    python -m lighthouse_tpu lcli skip-slots --slots 4
    python -m lighthouse_tpu db inspect --path chain.sqlite
"""

import argparse
import json
import sys
import time


def _spec_for(name: str, altair_epoch=None):
    """Spec for --network: built-in network-config assets first (the
    eth2_network_config path — mainnet/minimal/gnosis config.yaml dirs
    under lighthouse_tpu/network_configs/), programmatic presets as the
    fallback."""
    from dataclasses import replace

    from lighthouse_tpu import network_config as nc
    from lighthouse_tpu.types.spec import mainnet_spec, minimal_spec

    try:
        spec = nc.builtin(name).spec
    except nc.NetworkConfigError:
        spec = minimal_spec() if name == "minimal" else mainnet_spec()
    if altair_epoch is not None:
        spec = replace(spec, ALTAIR_FORK_EPOCH=altair_epoch)
    return spec


def _apply_store_flags(chain, args) -> None:
    """Store flags shared by every bn boot path (applied before any
    migration can run; slots_per_restore_point is only read at
    migrate/load time)."""
    if args.slots_per_restore_point:
        chain.store.slots_per_restore_point = args.slots_per_restore_point


def _apply_trace_flags(args) -> None:
    """Size (or disable, with 0) the data-plane span tracer before any
    chain work runs."""
    from lighthouse_tpu.common import tracing

    capacity = getattr(args, "trace_buffer", tracing.DEFAULT_CAPACITY)
    tracing.configure(enabled=capacity > 0, capacity=max(capacity, 1))


def _apply_journal_flags(chain, args) -> None:
    """Size (or disable, with 0) the node's lifecycle event journal;
    point the process compile ledger at its persistent JSONL file."""
    from lighthouse_tpu.common import events_journal

    capacity = getattr(
        args, "journal_buffer", events_journal.DEFAULT_CAPACITY
    )
    chain.journal.configure(
        enabled=capacity > 0, capacity=max(capacity, 1)
    )
    ledger_path = getattr(args, "compile_ledger", None)
    if ledger_path:
        from lighthouse_tpu.common.compile_ledger import LEDGER

        LEDGER.configure(path=ledger_path)


def parse_admission_limits(spec_str):
    """``cls=concurrency:deadline,...`` -> {cls: (int, float)}; classes
    must exist in the admission vocabulary (typos are errors, not
    silently-ignored knobs)."""
    from lighthouse_tpu.http_api.admission import DEFAULT_LIMITS

    if not spec_str:
        return {}
    out = {}
    for part in spec_str.split(","):
        part = part.strip()
        if not part:
            continue
        cls_, _, limits = part.partition("=")
        conc, _, budget = limits.partition(":")
        if cls_ not in DEFAULT_LIMITS:
            raise ValueError(
                f"unknown admission class {cls_!r} "
                f"(one of {sorted(DEFAULT_LIMITS)})"
            )
        out[cls_] = (int(conc), float(budget or DEFAULT_LIMITS[cls_][1]))
    return out


def parse_bus_deadlines(spec_str):
    """``consumer=seconds,...`` -> {consumer: float}; consumers must be
    in the closed attribution vocabulary."""
    from lighthouse_tpu.common.device_attribution import CONSUMERS

    if not spec_str:
        return {}
    out = {}
    for part in spec_str.split(","):
        part = part.strip()
        if not part:
            continue
        consumer, _, seconds = part.partition("=")
        if consumer not in CONSUMERS:
            raise ValueError(
                f"unknown bus consumer {consumer!r} "
                f"(one of {sorted(CONSUMERS)})"
            )
        out[consumer] = float(seconds)
    return out


def _apply_bus_flags(chain, args) -> None:
    """Verification-bus knobs (max hold, bucket fill target, per-class
    deadline budgets) — the control surface for the ROADMAP self-tuning
    item, mirrored live at /lighthouse/health."""
    bus = getattr(chain, "verification_bus", None)
    if bus is None:
        return
    hold = getattr(args, "bus_max_hold_ms", None)
    if hold is not None and hold >= 0:
        bus.max_hold_ms = float(hold)
    fill = getattr(args, "bus_fill_target", 0)
    if fill:
        bus.fill_target = int(fill)
    deadlines = getattr(args, "bus_deadlines", None)
    if deadlines:
        bus.class_budgets.update(parse_bus_deadlines(deadlines))


def _apply_breaker_flags(chain, args) -> None:
    """Device-plane fault-domain knobs: circuit-breaker tuning, canary
    mode, and the optional boot-time known-answer self-test — applied
    to the process-global guarded executor (one accelerator, one
    breaker), mirrored live at /lighthouse/health under
    `device_plane`."""
    from lighthouse_tpu.device_plane import GUARD

    kwargs = {}
    threshold = getattr(args, "device_breaker_threshold", None)
    if threshold is not None:
        kwargs["threshold"] = int(threshold)
    cooldown_ms = getattr(args, "device_breaker_cooldown_ms", None)
    if cooldown_ms is not None:
        kwargs["cooldown_s"] = float(cooldown_ms) / 1000.0
    canary = getattr(args, "device_breaker_canary", None)
    if canary is not None:
        kwargs["canary"] = canary
    selftest = getattr(args, "device_breaker_selftest", "off") == "on"
    kwargs["selftest"] = selftest
    GUARD.configure(**kwargs)
    if selftest:
        GUARD.self_test(journal=getattr(chain, "journal", None))


def _apply_slot_fuse_flag(chain, args) -> None:
    """bn --slot-fuse: one-dispatch slot programs (default on)."""
    if chain is None:
        return
    fuse = getattr(args, "slot_fuse", None)
    if fuse is not None:
        chain.slot_fuse = fuse == "on"


def _apply_slot_budget_flags(chain, args) -> None:
    """Slot-budget profiler knobs: the enable switch and the recent-
    imports ring size behind GET /lighthouse/slot_budget."""
    recorder = getattr(chain, "slot_budget", None)
    if recorder is None:
        return
    enabled = getattr(args, "slot_budget", None)
    ring = getattr(args, "slot_budget_ring", None)
    recorder.configure(
        enabled=None if enabled is None else enabled == "on",
        ring=ring,
    )


def _apply_admission_flags(srv, args) -> None:
    """PR 10's hand-set admission constants become a flag: per-class
    concurrency + deadline overrides on the live controller."""
    limits = parse_admission_limits(
        getattr(args, "admission_limits", None)
    )
    if limits:
        srv.admission.limits.update(limits)


def _export_trace(args, chain=None) -> None:
    """Dump the buffered span trees (and journal events) as JSONL on
    shutdown when asked."""
    path = getattr(args, "trace_jsonl", None)
    if path:
        from lighthouse_tpu.common.tracing import TRACER

        n = TRACER.export_jsonl(path)
        print(f"wrote {n} span trees to {path}")
    jpath = getattr(args, "journal_jsonl", None)
    if jpath and chain is not None:
        n = chain.journal.export_jsonl(jpath)
        print(f"wrote {n} journal events to {jpath}")


def _serve_api(chain, args, banner: str) -> int:
    """Start the HTTP API, print the banner, serve for --serve-seconds,
    stop — shared by every bn boot path."""
    from lighthouse_tpu.http_api import BeaconApiServer

    _apply_store_flags(chain, args)
    _apply_journal_flags(chain, args)
    _apply_bus_flags(chain, args)
    _apply_breaker_flags(chain, args)
    _apply_slot_fuse_flag(chain, args)
    _apply_slot_budget_flags(chain, args)
    srv = BeaconApiServer(
        chain, host=args.http_address, port=args.http_port
    )
    _apply_admission_flags(srv, args)
    srv.start()
    print(f"{banner}; HTTP API on {args.http_address}:{srv.port}")
    try:
        if args.serve_seconds:
            time.sleep(args.serve_seconds)
    finally:
        srv.stop()
        _export_trace(args, chain)
    return 0


def cmd_bn(args):
    """Run a beacon node: interop genesis, optional self-proposing (dev
    chain), HTTP API, per-slot timer loop."""
    import os

    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.http_api import BeaconApiServer
    from lighthouse_tpu.store import SqliteStore

    _apply_trace_flags(args)
    if args.purge_db and args.datadir:
        # fork_revert.rs:14-15 guidance: a node stuck on the wrong side
        # of a fork starts over. The SQLite WAL/SHM sidecars must go
        # too — a fresh db next to a stale -wal would REPLAY the purged
        # chain right back on open
        purged = False
        for path in (
            args.datadir,
            args.datadir + "-wal",
            args.datadir + "-shm",
        ):
            if os.path.exists(path):
                os.remove(path)
                purged = True
        if purged:
            print(f"purged {args.datadir}")
    kv = SqliteStore(args.datadir) if args.datadir else None
    if args.testnet_dir:
        # file-driven boot (--testnet-dir: config.yaml + genesis.ssz,
        # the eth2_network_config custom-directory path)
        from lighthouse_tpu import network_config as nc

        cfg = nc.load_dir(args.testnet_dir)
        genesis = cfg.genesis_state()
        if genesis is None:
            print(
                f"{args.testnet_dir}: no genesis.ssz "
                "(generate one with lcli new-testnet)",
                file=sys.stderr,
            )
            return 1
        chain = BeaconChain(
            genesis, cfg.spec, kv=kv, backend=args.bls_backend
        )
        return _serve_api(
            chain,
            args,
            f"booted network {cfg.name!r} from {args.testnet_dir} "
            f"(genesis_validators_root 0x"
            f"{bytes(genesis.genesis_validators_root).hex()[:12]}, "
            f"{len(cfg.boot_nodes or [])} boot nodes)",
        )
    spec = _spec_for(args.network)
    if (
        args.checkpoint_state
        or args.checkpoint_block
        or args.checkpoint_sync_url
    ):
        # weak-subjectivity boot (client/src/config.rs:31-34): trusted
        # finalized state + block, from SSZ files or fetched from a
        # trusted beacon node over the standard API
        from lighthouse_tpu.http_api.client import (
            ApiClientError,
            decode_checkpoint_pair,
            fetch_checkpoint,
        )

        if args.checkpoint_sync_url and (
            args.checkpoint_state or args.checkpoint_block
        ):
            print(
                "--checkpoint-sync-url and --checkpoint-state/"
                "--checkpoint-block are mutually exclusive",
                file=sys.stderr,
            )
            return 1
        try:
            if args.checkpoint_sync_url:
                state, block = fetch_checkpoint(
                    args.checkpoint_sync_url, spec
                )
            else:
                if not (args.checkpoint_state and args.checkpoint_block):
                    print(
                        "--checkpoint-state and --checkpoint-block are "
                        "required together",
                        file=sys.stderr,
                    )
                    return 1
                with open(args.checkpoint_state, "rb") as f:
                    raw_state = f.read()
                with open(args.checkpoint_block, "rb") as f:
                    raw_block = f.read()
                state, block = decode_checkpoint_pair(
                    raw_state, raw_block, spec
                )
        except ApiClientError as e:
            print(f"checkpoint sync failed: {e}", file=sys.stderr)
            return 1
        chain = BeaconChain.from_checkpoint(
            state, block, spec, kv=kv, backend=args.bls_backend
        )
        return _serve_api(
            chain,
            args,
            f"checkpoint boot at slot {state.slot} "
            f"(anchor 0x{chain.head_root.hex()[:12]})",
        )
    h = Harness(
        spec,
        args.validators,
        backend=args.bls_backend,
        genesis_time=int(time.time()) if args.slots == 0 else 0,
    )
    chain = BeaconChain(
        h.state.copy(), spec, kv=kv, backend=args.bls_backend
    )
    _apply_store_flags(chain, args)
    _apply_journal_flags(chain, args)
    _apply_bus_flags(chain, args)
    _apply_breaker_flags(chain, args)
    _apply_slot_fuse_flag(chain, args)
    _apply_slot_budget_flags(chain, args)
    srv = BeaconApiServer(
        chain, host=args.http_address, port=args.http_port
    )
    _apply_admission_flags(srv, args)
    srv.start()
    print(f"HTTP API on {args.http_address}:{srv.port}")
    try:
        if args.slots:
            from lighthouse_tpu.state_processing.per_block import (
                BlockSignatureStrategy,
            )

            for slot in range(1, args.slots + 1):
                # the producer signed this block itself; the node's
                # chain verifies every signature of it on import
                block = h.advance_slot_with_block(
                    slot, strategy=BlockSignatureStrategy.NO_VERIFICATION
                )
                chain.process_block(block)
                chain.set_slot(slot)
                print(
                    f"slot {slot} head=0x{chain.head_root.hex()[:12]} "
                    f"justified={chain.head_state.current_justified_checkpoint.epoch} "
                    f"finalized={chain.finalized_checkpoint.epoch}"
                )
            print("dev chain complete")
            if args.serve_seconds:
                time.sleep(args.serve_seconds)
        else:
            while True:  # pragma: no cover
                time.sleep(spec.SECONDS_PER_SLOT)
    finally:
        srv.stop()
        _export_trace(args, chain)
    return 0


def build_http_vc(
    urls, keypairs, spec, slashing_db_path=None, use_builder=False
):
    """The `vc --beacon-node-url` wiring: one URL talks straight to a
    BeaconNodeHttpClient, several wrap in BeaconNodeFallback (health
    ranking + per-request failover) behind the same client surface.
    Returns a ready HttpValidatorClient."""
    from lighthouse_tpu.http_api.client import BeaconNodeHttpClient
    from lighthouse_tpu.validator_client.beacon_node_fallback import (
        BeaconNodeFallback,
        FallbackBeaconNodeClient,
    )
    from lighthouse_tpu.validator_client.http_vc import (
        HttpValidatorClient,
    )
    from lighthouse_tpu.validator_client.slashing_protection import (
        SlashingProtectionDB,
    )

    clients = [BeaconNodeHttpClient(u) for u in urls]
    if len(clients) == 1:
        client = clients[0]
    else:
        fallback = BeaconNodeFallback.from_clients(clients)
        fallback.update_health()
        client = FallbackBeaconNodeClient(fallback)
    return HttpValidatorClient(
        client,
        list(keypairs),
        spec,
        slashing_db=SlashingProtectionDB(slashing_db_path or ":memory:"),
        use_builder=use_builder,
    )


def _cmd_vc_http(args):
    """Run the HTTP-only duty loop against live beacon node(s): the VC
    reaches the BN exclusively over the REST API (validator_client/
    src/lib.rs production shape), following the BN's own genesis clock."""
    from lighthouse_tpu import bls

    spec = _spec_for(args.network)
    keypairs = bls.interop_keypairs(args.validators)
    vc = build_http_vc(
        args.beacon_node_url, keypairs, spec,
        slashing_db_path=args.slashing_db,
    )
    genesis_time = int(vc.client.get_genesis()["genesis_time"])
    sps = spec.SECONDS_PER_SLOT
    start_slot = max(1, (int(time.time()) - genesis_time) // sps + 1)
    for slot in range(start_slot, start_slot + args.slots):
        wait = genesis_time + slot * sps - time.time()
        if wait > 0:
            time.sleep(wait)
        vc.run_slot(slot)
    print(
        json.dumps(
            {
                "slots": args.slots,
                "beacon_nodes": list(args.beacon_node_url),
                "proposed": vc.metrics["blocks_proposed"],
                "attestations": vc.metrics["attestations_published"],
                "aggregates": vc.metrics["aggregates_published"],
                "publish_errors": vc.metrics["publish_errors"],
            }
        )
    )
    return 0


def cmd_vc(args):
    """Run validator duties: against live beacon node(s) over HTTP when
    --beacon-node-url is given (repeat the flag for a ranked fallback
    list), else against an in-process dev node for N slots."""
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.beacon_chain import BeaconChain
    from lighthouse_tpu.validator_client import (
        SlashingProtectionDB,
        ValidatorClient,
    )

    if args.beacon_node_url:
        return _cmd_vc_http(args)
    spec = _spec_for(args.network)
    h = Harness(spec, args.validators, backend=args.bls_backend)
    chain = BeaconChain(h.state.copy(), spec, backend=args.bls_backend)
    db = SlashingProtectionDB(args.slashing_db or ":memory:")
    vc = ValidatorClient(
        chain, dict(enumerate(h.keypairs)), slashing_db=db
    )

    def producer(slot, proposer):
        blk = h.produce_block(slot, h.pending_attestations[:128])
        h.pending_attestations = h.pending_attestations[128:]
        return blk.message

    for slot in range(1, args.slots + 1):
        chain.set_slot(slot)
        signed = vc.propose(slot, producer)
        if signed is not None:
            chain.process_block(signed)
            h.import_block(signed)
        atts = vc.attest(slot)
        chain.process_unaggregated_attestations(atts)
        h.pending_attestations.extend(
            chain.naive_pool.aggregates_at_slot(slot)
        )
    print(
        json.dumps(
            {
                "slots": args.slots,
                "proposed": vc.metrics["blocks_proposed"],
                "attestations": vc.metrics["attestations_published"],
                "finalized_epoch": chain.finalized_checkpoint.epoch,
            }
        )
    )
    return 0


def cmd_account(args):
    from lighthouse_tpu import bls
    from lighthouse_tpu.accounts import (
        Keystore,
        derive_path,
        mnemonic_to_seed,
    )

    if args.account_cmd == "new":
        if args.mnemonic:
            seed = mnemonic_to_seed(args.mnemonic)
            sk_int = derive_path(seed, f"m/12381/3600/{args.index}/0")
            sk = bls.SecretKey(sk_int)
        else:
            sk = bls.SecretKey.random()
        pk = sk.public_key()
        ks = Keystore.encrypt(
            sk.to_bytes(),
            args.password,
            path=f"m/12381/3600/{args.index}/0",
            kdf=args.kdf,
            pubkey=pk.to_bytes(),
        )
        payload = ks.to_json()
        if args.out:
            with open(args.out, "w") as f:
                f.write(payload)
        else:
            print(payload)
        print(f"pubkey: 0x{pk.to_bytes().hex()}", file=sys.stderr)
        return 0
    if args.account_cmd == "import":
        with open(args.keystore) as f:
            ks = Keystore.from_json(f.read())
        secret = ks.decrypt(args.password)
        sk = bls.SecretKey.from_bytes(secret)
        print(f"imported 0x{sk.public_key().to_bytes().hex()}")
        return 0
    if args.account_cmd == "wallet-create":
        from lighthouse_tpu.accounts.wallet import Wallet

        w = Wallet.create(
            args.name, args.password, mnemonic=args.mnemonic,
            seed=bytes.fromhex(args.seed) if args.seed else None,
            kdf=args.kdf,
        )
        with open(args.out or f"{args.name}.wallet.json", "w") as f:
            f.write(w.to_json())
        print(json.dumps({"wallet": w.name, "nextaccount": w.nextaccount}))
        return 0
    if args.account_cmd == "wallet-next":
        from lighthouse_tpu.accounts.wallet import Wallet

        if not args.wallet:
            raise SystemExit("wallet-next requires --wallet <file>")
        with open(args.wallet) as f:
            w = Wallet.from_json(f.read())
        index, ks, _wd = w.next_validator(
            args.password, args.keystore_password or args.password
        )
        # keystore first, wallet (with the bumped counter) last — a
        # keystore write failure must not burn the account index
        out = args.out or f"validator_{index}.keystore.json"
        with open(out, "w") as f:
            f.write(ks.to_json())
        with open(args.wallet, "w") as f:
            f.write(w.to_json())
        print(
            json.dumps(
                {"index": index, "pubkey": "0x" + ks.pubkey_hex, "out": out}
            )
        )
        return 0
    raise SystemExit(f"unknown account command {args.account_cmd}")


def cmd_lcli(args):
    from lighthouse_tpu.harness import Harness
    from lighthouse_tpu.state_processing.per_slot import process_slots

    spec = _spec_for(args.network)
    if args.lcli_cmd == "skip-slots":
        h = Harness(spec, args.validators)
        state = process_slots(h.state, args.slots, spec)
        print(
            json.dumps(
                {
                    "slot": state.slot,
                    "state_root": "0x"
                    + type(state).hash_tree_root(state).hex(),
                }
            )
        )
        return 0
    if args.lcli_cmd == "transition-blocks":
        h = Harness(spec, args.validators)
        h.run_slots(args.slots)
        print(
            json.dumps(
                {
                    "slot": h.state.slot,
                    "state_root": "0x"
                    + type(h.state).hash_tree_root(h.state).hex(),
                    "finalized_epoch": h.finalized_epoch,
                }
            )
        )
        return 0
    if args.lcli_cmd == "new-testnet":
        from lighthouse_tpu import bls

        kps = bls.interop_keypairs(args.validators)
        from lighthouse_tpu.state_processing.genesis import (
            interop_genesis_state,
        )

        state = interop_genesis_state(
            [k.pk.to_bytes() for k in kps], args.genesis_time, spec
        )
        if args.testnet_dir:
            # full network directory (config.yaml + genesis.ssz) that
            # `bn --testnet-dir` boots from — new_testnet in lcli
            from lighthouse_tpu import network_config as nc

            nc.write_dir(args.testnet_dir, spec, genesis_state=state)
            print(
                json.dumps(
                    {
                        "testnet_dir": args.testnet_dir,
                        "genesis_validators_root": "0x"
                        + bytes(state.genesis_validators_root).hex(),
                    }
                )
            )
            return 0
        data = state.to_bytes()
        with open(args.out, "wb") as f:
            f.write(data)
        print(
            json.dumps(
                {
                    "genesis_validators_root": "0x"
                    + bytes(state.genesis_validators_root).hex(),
                    "bytes": len(data),
                }
            )
        )
        return 0
    raise SystemExit(f"unknown lcli command {args.lcli_cmd}")


def cmd_db(args):
    from lighthouse_tpu.store import SqliteStore

    kv = SqliteStore(args.path)
    if args.db_cmd == "inspect":
        from lighthouse_tpu.store.hot_cold import (
            COL_BLOCK,
            COL_COLD_STATE,
            COL_HOT_STATE,
        )
        from lighthouse_tpu.store.schema import get_schema_version

        print(
            json.dumps(
                {
                    "schema_version": get_schema_version(kv),
                    "blocks": len(kv.keys(COL_BLOCK)),
                    "hot_states": len(kv.keys(COL_HOT_STATE)),
                    "cold_states": len(kv.keys(COL_COLD_STATE)),
                }
            )
        )
        return 0
    if args.db_cmd == "version":
        from lighthouse_tpu.store.schema import (
            CURRENT_SCHEMA_VERSION,
            get_schema_version,
        )

        print(
            json.dumps(
                {
                    "schema_version": get_schema_version(kv),
                    "current": CURRENT_SCHEMA_VERSION,
                }
            )
        )
        return 0
    if args.db_cmd == "migrate":
        from lighthouse_tpu.store.schema import (
            CURRENT_SCHEMA_VERSION,
            migrate_schema,
        )

        target = (
            args.target if args.target is not None
            else CURRENT_SCHEMA_VERSION
        )
        final = migrate_schema(kv, target=target)
        print(json.dumps({"schema_version": final}))
        return 0
    raise SystemExit(f"unknown db command {args.db_cmd}")


def cmd_boot_node(args):
    """Standalone bootstrap-node entry point (`lighthouse boot_node`,
    boot_node/src). The registry here is in-process: simulated nodes join
    it directly (network.discovery.BootstrapRegistry is how the node-sim
    wires discovery); there is no wire listener yet."""
    from lighthouse_tpu.network.discovery import (
        BootstrapRegistry,
        PeerRecord,
    )

    registry = BootstrapRegistry()
    node_id = args.node_id or "boot"
    registry.register(PeerRecord(node_id=node_id))
    print(
        json.dumps(
            {
                "node_id": node_id,
                "role": "boot_node",
                "peers": len(registry.records),
            }
        )
    )
    if args.serve_seconds:
        time.sleep(args.serve_seconds)
    return 0


def build_parser():
    p = argparse.ArgumentParser(prog="lighthouse_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    bn = sub.add_parser("bn", help="beacon node")
    bn.add_argument("--network", default="minimal")
    bn.add_argument("--validators", type=int, default=32)
    bn.add_argument("--slots", type=int, default=8)
    bn.add_argument("--http-port", type=int, default=0)
    bn.add_argument("--http-address", default="127.0.0.1")
    bn.add_argument("--datadir", default=None)
    bn.add_argument(
        "--purge-db",
        action="store_true",
        help="delete the datadir before boot (fork-revert recovery)",
    )
    bn.add_argument(
        "--slots-per-restore-point",
        type=int,
        default=0,
        help="freezer restore-point interval (0 = spec default)",
    )
    bn.add_argument("--bls-backend", default="ref")
    bn.add_argument("--serve-seconds", type=float, default=0)
    bn.add_argument(
        "--checkpoint-state",
        default=None,
        help="SSZ file with a trusted finalized state (checkpoint sync)",
    )
    bn.add_argument(
        "--checkpoint-block",
        default=None,
        help="SSZ file with the block matching --checkpoint-state",
    )
    bn.add_argument(
        "--testnet-dir",
        default=None,
        help="network directory (config.yaml + genesis.ssz) to boot from",
    )
    bn.add_argument(
        "--checkpoint-sync-url",
        default=None,
        help="trusted beacon node URL to fetch the finalized "
        "state/block from (weak-subjectivity boot)",
    )
    bn.add_argument(
        "--trace-buffer",
        type=int,
        default=256,
        help="span-tracer ring capacity in root spans, served at GET "
        "/lighthouse/spans (0 disables span-tree buffering; the "
        "*_stage_seconds histograms keep recording)",
    )
    bn.add_argument(
        "--trace-jsonl",
        default=None,
        help="write the buffered span trees to this JSONL file on "
        "shutdown (bench attribution input)",
    )
    bn.add_argument(
        "--journal-buffer",
        type=int,
        default=4096,
        help="lifecycle event-journal ring capacity, served at GET "
        "/lighthouse/events (0 disables the journal entirely; the "
        "underlying subsystem counters keep counting)",
    )
    bn.add_argument(
        "--journal-jsonl",
        default=None,
        help="write the buffered journal events to this JSONL file on "
        "shutdown (chaos-run forensics input)",
    )
    bn.add_argument(
        "--compile-ledger",
        default=None,
        help="append every COLD jit (re)compile event to this "
        "persistent JSONL ledger (warm dispatches stay in the "
        "in-memory ring served at GET /lighthouse/compiles; env "
        "LIGHTHOUSE_TPU_COMPILE_LEDGER is the flagless spelling)",
    )
    bn.add_argument(
        "--admission-limits",
        default=None,
        help="per-class HTTP admission overrides, "
        "'cls=concurrency:deadline_s,...' (classes: cheap_read, "
        "expensive_read, write) — the PR 10 hand-set constants as a "
        "control surface, mirrored at /lighthouse/health",
    )
    bn.add_argument(
        "--bus-max-hold-ms",
        type=float,
        default=None,
        help="verification bus: maximum milliseconds a submission may "
        "hold waiting for co-riders (default: 25 on the tpu backend, "
        "0 — attributed passthrough — on host backends)",
    )
    bn.add_argument(
        "--bus-fill-target",
        type=int,
        default=0,
        help="verification bus: pending live sets that close a batch "
        "(one pow2 lane bucket's worth; 0 keeps the default 64)",
    )
    bn.add_argument(
        "--bus-deadlines",
        default=None,
        help="verification bus per-class deadline budgets, "
        "'consumer=seconds,...' over the closed consumer vocabulary "
        "(gossip classes default to the slot clock's 1/3-slot window)",
    )
    bn.add_argument(
        "--device-breaker-threshold",
        type=int,
        default=None,
        help="device-plane circuit breaker: consecutive faults on a "
        "(plane, shape-bucket) that open it (default 3)",
    )
    bn.add_argument(
        "--device-breaker-cooldown-ms",
        type=float,
        default=None,
        help="device-plane circuit breaker: milliseconds an open "
        "breaker waits before admitting one half-open probe "
        "(default 30000)",
    )
    bn.add_argument(
        "--device-breaker-canary",
        choices=["auto", "on", "off"],
        default=None,
        help="canary sentinel checks on shared device batches: auto "
        "(tpu backend or armed fault injection — the default), on, "
        "or off",
    )
    bn.add_argument(
        "--slot-fuse",
        choices=["on", "off"],
        default=None,
        help="one-dispatch slot: chain tree-hash, signature fold and "
        "KZG settle of a blob import into a single guarded device "
        "dispatch (default on; off restores the serial "
        "three-dispatch path)",
    )
    bn.add_argument(
        "--slot-budget",
        choices=["on", "off"],
        default=None,
        help="slot-budget profiler: per-import critical-path recording "
        "behind GET /lighthouse/slot_budget (default on; off skips "
        "even the per-import begin/finish bookkeeping)",
    )
    bn.add_argument(
        "--slot-budget-ring",
        type=int,
        default=None,
        help="recent-import waterfalls kept for /lighthouse/slot_budget "
        "(default 128)",
    )
    bn.add_argument(
        "--device-breaker-selftest",
        choices=["on", "off"],
        default="off",
        help="run the per-plane known-answer self-test at boot; a "
        "failing plane starts quarantined on host tiers (default off)",
    )
    bn.set_defaults(fn=cmd_bn)

    vc = sub.add_parser("vc", help="validator client")
    vc.add_argument("--network", default="minimal")
    vc.add_argument("--validators", type=int, default=32)
    vc.add_argument("--slots", type=int, default=8)
    vc.add_argument("--slashing-db", default=None)
    vc.add_argument("--bls-backend", default="ref")
    vc.add_argument(
        "--beacon-node-url",
        action="append",
        default=None,
        help="beacon node REST URL; repeat for a ranked fallback list "
        "— the VC then talks HTTP only (HttpValidatorClient), never "
        "an in-process chain",
    )
    vc.set_defaults(fn=cmd_vc)

    acct = sub.add_parser("account", help="keys & keystores")
    acct.add_argument(
        "account_cmd",
        choices=["new", "import", "wallet-create", "wallet-next"],
    )
    acct.add_argument("--password", required=True)
    acct.add_argument("--kdf", default="pbkdf2")
    acct.add_argument("--mnemonic", default=None)
    acct.add_argument("--seed", default=None)
    acct.add_argument("--index", type=int, default=0)
    acct.add_argument("--name", default="wallet")
    acct.add_argument("--wallet", default=None)
    acct.add_argument("--keystore-password", default=None)
    acct.add_argument("--out", default=None)
    acct.add_argument("--keystore", default=None)
    acct.set_defaults(fn=cmd_account)

    lcli = sub.add_parser("lcli", help="dev tools")
    lcli.add_argument(
        "lcli_cmd",
        choices=["skip-slots", "transition-blocks", "new-testnet"],
    )
    lcli.add_argument("--network", default="minimal")
    lcli.add_argument("--validators", type=int, default=16)
    lcli.add_argument("--slots", type=int, default=8)
    lcli.add_argument("--genesis-time", type=int, default=0)
    lcli.add_argument("--out", default="genesis.ssz")
    lcli.add_argument(
        "--testnet-dir",
        default=None,
        help="write a full network dir (config.yaml + genesis.ssz)",
    )
    lcli.set_defaults(fn=cmd_lcli)

    db = sub.add_parser("db", help="database tools")
    db.add_argument("db_cmd", choices=["inspect", "version", "migrate"])
    db.add_argument("--path", required=True)
    db.add_argument("--target", type=int, default=None)
    db.set_defaults(fn=cmd_db)

    boot = sub.add_parser("boot_node", help="discovery bootstrap node")
    boot.add_argument("--node-id", default=None)
    boot.add_argument("--serve-seconds", type=float, default=0)
    boot.set_defaults(fn=cmd_boot_node)
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
