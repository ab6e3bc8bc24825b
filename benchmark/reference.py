"""The plain reference: Automerge 0.9 change semantics for one document of
maps whose history is totally ordered (every change depends on everything
its document had seen when it was made), written from the protocol alone.

It applies changes one at a time, in order: a map takes the last value
set on a key, ``del`` removes the key, ``link`` nests a map made by
``makeMap``. ``plain()`` renders the document as the program's views
render it, maps as dicts. A change that arrives out of order, or that is
concurrent with what the document has seen, raises, as does any op on a
list or text: such traffic is outside this reference.
"""

ROOT_ID = '00000000-0000-0000-0000-000000000000'


class RefDoc:
    """One document under the reference semantics."""

    def __init__(self):
        self.objects = {ROOT_ID: {}}
        self.clock = {}

    def apply(self, change):
        actor, seq = change['actor'], change['seq']
        if seq != self.clock.get(actor, 0) + 1:
            raise ValueError(f'{actor}:{seq} out of order')
        seen = dict(change['deps'])
        seen[actor] = seq - 1
        for a, s in self.clock.items():
            if seen.get(a, 0) < s:
                raise ValueError(f'{actor}:{seq} is concurrent with '
                                 f'{a}:{s}')
        self.clock[actor] = seq
        for op in change['ops']:
            self._op(op)

    def _op(self, op):
        action, obj = op['action'], op['obj']
        if action == 'makeMap':
            self.objects[obj] = {}
        elif action == 'set':
            self.objects[obj][op['key']] = ('value', op.get('value'))
        elif action == 'link':
            self.objects[obj][op['key']] = ('link', op['value'])
        elif action == 'del':
            self.objects[obj].pop(op['key'], None)
        else:
            raise ValueError(f'{action} is outside this reference')

    def plain(self):
        return self._render(ROOT_ID)

    def _render(self, obj):
        return {k: self._render(x) if kind == 'link' else x
                for k, (kind, x) in self.objects[obj].items()}


def replay(changes):
    doc = RefDoc()
    for c in changes:
        doc.apply(c)
    return doc
