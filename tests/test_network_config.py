"""Network-config directories: YAML spec round-trip, embedded assets,
testnet-dir write/load, and the CLI boot path.

Mirrors common/eth2_network_config + config_and_preset.rs: a network is a
directory of config.yaml (+ genesis.ssz + boot_nodes.yaml) and both the
built-ins and --testnet-dir go through one loader.
"""

from lighthouse_tpu import network_config as nc
from lighthouse_tpu.types.spec import (
    gnosis_spec,
    mainnet_spec,
    minimal_spec,
    spec_from_config_yaml,
    spec_to_config_yaml,
)


def test_config_yaml_round_trip_all_presets():
    for mk in (mainnet_spec, minimal_spec, gnosis_spec):
        spec = mk()
        assert spec_from_config_yaml(spec_to_config_yaml(spec)) == spec


def test_config_yaml_round_trip_with_overrides():
    spec = minimal_spec(
        SECONDS_PER_SLOT=3,
        ALTAIR_FORK_EPOCH=7,
        GENESIS_FORK_VERSION=bytes.fromhex("deadbeef"),
    )
    rt = spec_from_config_yaml(spec_to_config_yaml(spec))
    assert rt.SECONDS_PER_SLOT == 3
    assert rt.ALTAIR_FORK_EPOCH == 7
    assert rt.GENESIS_FORK_VERSION == bytes.fromhex("deadbeef")
    assert rt == spec


def test_builtin_networks_ship_and_load():
    names = nc.builtin_names()
    assert {"mainnet", "minimal", "gnosis"} <= set(names)
    for name in names:
        cfg = nc.builtin(name)
        assert cfg.spec.name == name
    assert nc.builtin("gnosis").spec.SECONDS_PER_SLOT == 5


def test_testnet_dir_write_load_and_genesis(tmp_path):
    from lighthouse_tpu import bls
    from lighthouse_tpu.state_processing.genesis import (
        interop_genesis_state,
    )

    spec = minimal_spec(ALTAIR_FORK_EPOCH=2**64 - 1)
    kps = bls.interop_keypairs(8)
    state = interop_genesis_state(
        [k.pk.to_bytes() for k in kps], 0, spec
    )
    d = str(tmp_path / "net")
    nc.write_dir(
        d, spec, genesis_state=state, boot_nodes=["127.0.0.1:9000"]
    )
    cfg = nc.load_dir(d)
    assert cfg.spec == spec
    assert cfg.boot_nodes == ["127.0.0.1:9000"]
    loaded = cfg.genesis_state()
    assert bytes(loaded.genesis_validators_root) == bytes(
        state.genesis_validators_root
    )


def test_cli_bn_boots_from_testnet_dir(tmp_path, capsys):
    """python -m lighthouse_tpu bn --testnet-dir X boots from files
    (the VERDICT's done-criterion for the config system)."""
    from lighthouse_tpu.cli import main

    d = str(tmp_path / "net")
    rc = main(
        [
            "lcli",
            "new-testnet",
            "--validators",
            "8",
            "--testnet-dir",
            d,
        ]
    )
    assert rc == 0
    rc = main(["bn", "--testnet-dir", d])
    assert rc == 0
    out = capsys.readouterr().out
    assert "booted network 'minimal'" in out


def test_bn_dev_chain_imports_every_slot(capsys):
    """`bn --slots N`: the dev producer signs each block and the node's
    chain verifies and imports it — the head moves every slot."""
    import re

    from lighthouse_tpu.cli import main

    rc = main([
        "bn", "--validators", "16", "--slots", "2",
        "--bls-backend", "ref", "--http-port", "0",
    ])
    out = capsys.readouterr().out
    assert rc == 0 and "dev chain complete" in out
    heads = re.findall(r"^slot (\d+) head=0x([0-9a-f]+)", out, re.M)
    assert [int(s) for s, _ in heads] == [1, 2]
    assert heads[0][1] != heads[1][1]
