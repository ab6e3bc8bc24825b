"""YCSB's core workload, as Automerge changes to a fleet of documents.

One YCSB record is one document: its ``fieldcount`` fields are keys
``field0``.. of the document's root map, each set to a string of
``fieldlength`` characters. The load phase inserts every record in one
change (``fieldcount`` set ops). The run phase draws operations as YCSB's
``CoreWorkload`` does: read or update by ``readproportion``, the record
by YCSB's scrambled Zipfian generator, an update's one field uniformly
(``writeallfields=false``) and its new value at random. An update is one
change (one set op) that depends on everything its document has seen.

The generators follow YCSB's own code (``site.ycsb.Utils.fnvhash64``,
``ZipfianGenerator``, ``ScrambledZipfianGenerator``, ``RandomByteIterator``,
``CoreWorkload.buildKeyName``), drawn from numpy's generator in place of
Java's thread-local random source.
"""

import numpy as np

ROOT_ID = '00000000-0000-0000-0000-000000000000'
LOAD_ACTOR = 'load'
CLIENT_ACTOR = 'client'

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
# ScrambledZipfianGenerator: a Zipfian over ITEM_COUNT items, with the
# zeta constant YCSB precomputes for that count at constant 0.99
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
USED_ZIPFIAN_CONSTANT = 0.99


def fnvhash64(values):
    """``Utils.fnvhash64`` over an array of non-negative longs: FNV-1a
    over the value's eight bytes, low byte first, then ``Math.abs``."""
    v = np.asarray(values, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over='ignore'):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= prime
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def key_names(n):
    """``buildKeyName`` for record numbers ``0..n-1`` with YCSB's default
    ``insertorder=hashed``: ``'user'`` plus the record number's hash."""
    return [f'user{h}' for h in fnvhash64(np.arange(n)).tolist()]


def zipfian(u, items, theta, zetan):
    """``ZipfianGenerator.nextLong`` for uniform draws ``u`` over
    ``items`` items (Gray et al., SIGMOD 1994)."""
    zeta2theta = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2theta / zetan)
    uz = u * zetan
    ret = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    ret = np.where(uz < 1.0 + 0.5 ** theta, 1, ret)
    return np.where(uz < 1.0, 0, ret)


def scrambled_zipfian(rng, size, n_records, theta):
    """Record numbers as ``CoreWorkload`` chooses them under
    ``requestdistribution=zipfian``: a ``ScrambledZipfianGenerator`` over
    ``0..n_records`` (``insertstart + insertcount``), and a draw past the
    last inserted record drawn again."""
    if theta != USED_ZIPFIAN_CONSTANT:
        raise ValueError('only the precomputed constant 0.99 is supported')
    out = np.empty(size, dtype=np.int64)
    todo = np.arange(size)
    while todo.size:
        z = zipfian(rng.random(todo.size), ITEM_COUNT + 1, theta, ZETAN)
        k = fnvhash64(z) % (n_records + 1)
        ok = k < n_records
        out[todo[ok]] = k[ok]
        todo = todo[~ok]
    return out


def random_strings(rng, count, length):
    """``count`` values as ``RandomByteIterator`` fills them: each
    character ``' '`` plus six random bits masked by 95."""
    raw = (rng.integers(0, 256, count * length, dtype=np.uint8)
           & np.uint8(95)) + np.uint8(32)
    text = raw.tobytes().decode('ascii')
    return [text[i:i + length] for i in range(0, count * length, length)]


def load_changes(n_records, fieldcount, fieldlength, seed):
    """The load phase: per record one change of ``fieldcount`` sets.
    Returns ``(doc_ids, changes)``, ``changes[i]`` the record's history
    as a one-change list."""
    rng = np.random.default_rng([seed, 1])
    ids = key_names(n_records)
    values = random_strings(rng, n_records * fieldcount, fieldlength)
    changes = []
    for r in range(n_records):
        base = r * fieldcount
        changes.append([{'actor': LOAD_ACTOR, 'seq': 1, 'deps': {},
                         'ops': [{'action': 'set', 'obj': ROOT_ID,
                                  'key': f'field{f}',
                                  'value': values[base + f]}
                                 for f in range(fieldcount)]}])
    return ids, changes


def run_ticks(config, n_ticks, ops_per_tick, seed):
    """The run phase: ``n_ticks`` ticks of ``ops_per_tick`` operations.

    Returns ``[(updates, reads, n_ops)]``: ``updates`` maps a doc id to
    the tick's update changes of that doc in order, ``reads`` lists the
    docs the tick's reads ask for (each once, in first-asked order) and
    ``n_ops`` counts the Automerge ops the updates carry. The server
    applies a tick's updates, then answers its reads."""
    rng = np.random.default_rng([seed, 2])
    n = config['recordcount']
    total = n_ticks * ops_per_tick
    if config['readproportion'] + config['updateproportion'] != 1.0:
        raise ValueError('only reads and updates are supported')
    if config['requestdistribution'] != 'zipfian':
        raise ValueError('only requestdistribution=zipfian is supported')
    reads = rng.random(total) < config['readproportion']
    records = scrambled_zipfian(rng, total, n, config['zipfianconstant'])
    fields = rng.integers(0, config['fieldcount'], total)
    values = random_strings(rng, int((~reads).sum()),
                            config['fieldlength'])
    ids = key_names(n)
    seqs = {}
    ticks = []
    v = 0
    for t in range(n_ticks):
        updates = {}
        asked = {}
        for k in range(t * ops_per_tick, (t + 1) * ops_per_tick):
            doc_id = ids[int(records[k])]
            if reads[k]:
                asked[doc_id] = None
                continue
            seq = seqs[doc_id] = seqs.get(doc_id, 0) + 1
            updates.setdefault(doc_id, []).append(
                {'actor': CLIENT_ACTOR, 'seq': seq,
                 'deps': {LOAD_ACTOR: 1},
                 'ops': [{'action': 'set', 'obj': ROOT_ID,
                          'key': f'field{int(fields[k])}',
                          'value': values[v]}]})
            v += 1
        n_ops = sum(len(cs) for cs in updates.values())
        ticks.append((updates, list(asked), n_ops))
    return ticks
