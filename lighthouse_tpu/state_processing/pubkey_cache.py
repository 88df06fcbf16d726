"""Decompressed validator pubkey cache.

Role of the reference's `ValidatorPubkeyCache`
(beacon_node/beacon_chain/src/validator_pubkey_cache.rs:9-24): pubkey
decompression is expensive; do it once per validator and reuse across every
signature-set build. Each cached key is tagged with its validator index and
the owning cache, so the TPU backend can ship (table, indices) instead of
points (the device half lives in bls/device_pubkey_table.py).
"""

from lighthouse_tpu import bls


class PubkeyCache:
    def __init__(self):
        self._by_index: list[bls.PublicKey] = []
        self._by_bytes: dict[bytes, int] = {}
        self._device_table = None  # built by device_table(), then synced

    def import_new(self, state):
        """Pick up any validators appended since the last import."""
        start = len(self._by_index)
        for i in range(start, len(state.validators)):
            pk_bytes = bytes(state.validators[i].pubkey)
            pk = bls.PublicKey.from_bytes(pk_bytes)
            pk.validator_index = i
            pk.cache = self
            self._by_index.append(pk)
            self._by_bytes[pk_bytes] = i
        if self._device_table is not None and len(self._by_index) > start:
            self._device_table.append(self._by_index[start:])

    def device_table(self):
        """The device-resident limb table, synced to the cache. The first
        call builds it from every cached key, a start-up cost (the chain
        pays it when it starts on the TPU backend); import_new appends to
        it from then on."""
        from lighthouse_tpu.bls.device_pubkey_table import DevicePubkeyTable

        if self._device_table is None:
            table = DevicePubkeyTable()
            table.append(self._by_index)
            table.rows()
            self._device_table = table
        return self._device_table

    def ready_table(self):
        """The device table when it is built and holds every cached key,
        else None. Never builds it: a signature batch only reads it."""
        table = self._device_table
        if table is None or table.count != len(self._by_index):
            return None
        return table

    def get(self, index: int) -> bls.PublicKey:
        return self._by_index[index]

    def get_by_bytes(self, pk_bytes: bytes) -> bls.PublicKey:
        return self._by_index[self._by_bytes[bytes(pk_bytes)]]

    def index_of(self, pk_bytes: bytes):
        return self._by_bytes.get(bytes(pk_bytes))

    def __len__(self):
        return len(self._by_index)
