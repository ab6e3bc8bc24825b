"""The served path: the program's calls that the window times.

A request kind (``kinds/<kind>.py``) drives the program only through
such a path, so that the control and the fault tests can put another in
its place.
"""


class DocSetPath:
    """A fleet of documents in one ``GeneralDocSet``."""

    def new(self, n_docs):
        from automerge_tpu.sync.general_doc_set import GeneralDocSet
        return GeneralDocSet(n_docs)

    def apply(self, doc_set, batch):
        doc_set.apply_changes_batch(batch)

    def read(self, doc_set, doc_ids):
        return doc_set.materialize_many(doc_ids)
