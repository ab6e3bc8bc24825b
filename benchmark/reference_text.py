"""The plain reference for texts: Automerge 0.9 list semantics for one
document, written from the protocol alone.

A document holds maps and texts. ``makeText`` makes a text and ``link``
puts it under a map key; ``set`` on a map key sets it. In a text, ``ins``
makes the element ``<actor>:<elem>`` after the element named by its
``key`` (``_head`` for the start), and ``set`` on an element id gives
the element its character. The children of one element are ordered by
descending ``(elem, actor)``, and the text reads in depth-first order
from the head: a newer insert at a position goes before the older ones
there, and concurrent inserts at one position by actor, the greater
first. An element no ``set`` has reached is not shown.

Changes must arrive causally: a change's seq follows its actor's last,
and every change it depends on has been applied. Concurrent changes are
allowed. ``del``, a second ``set`` of one element and any other action
raise: such traffic is outside this reference. ``plain()`` renders the
document as the program's views render it, maps as dicts and each text
as one string.
"""

import bisect

ROOT_ID = '00000000-0000-0000-0000-000000000000'


class _Text:
    def __init__(self):
        # element id -> its children as ascending (elem, actor, id):
        # the walk takes them from the end, descending
        self.children = {'_head': []}
        self.values = {}


class RefTextDoc:
    """One document under the reference semantics."""

    def __init__(self):
        self.maps = {ROOT_ID: {}}
        self.texts = {}
        self.clock = {}

    def apply(self, change):
        actor, seq = change['actor'], change['seq']
        if seq != self.clock.get(actor, 0) + 1:
            raise ValueError(f'{actor}:{seq} out of order')
        for a, s in change['deps'].items():
            if self.clock.get(a, 0) < s:
                raise ValueError(f'{actor}:{seq} depends on {a}:{s}, '
                                 'which has not arrived')
        self.clock[actor] = seq
        for op in change['ops']:
            self._op(actor, op)

    def _op(self, actor, op):
        action, obj = op['action'], op['obj']
        if action == 'makeText':
            self.texts[obj] = _Text()
        elif obj in self.texts:
            text = self.texts[obj]
            if action == 'ins':
                elem = op['elem']
                eid = f'{actor}:{elem}'
                if eid in text.children:
                    raise ValueError(f'{eid} inserted twice')
                bisect.insort(text.children[op['key']], (elem, actor, eid))
                text.children[eid] = []
            elif action == 'set' and op['key'] not in text.values:
                if op['key'] not in text.children:
                    raise ValueError(f"set of unknown element {op['key']}")
                text.values[op['key']] = op['value']
            else:
                raise ValueError(f'{action} on {op["key"]} is outside '
                                 'this reference')
        elif action == 'set':
            self.maps[obj][op['key']] = ('value', op.get('value'))
        elif action == 'link':
            self.maps[obj][op['key']] = ('link', op['value'])
        else:
            raise ValueError(f'{action} is outside this reference')

    def elements(self, obj):
        """The ids of the text's shown elements, in document order."""
        t = self.texts[obj]
        out = []
        stack = [e for _, _, e in t.children['_head']]
        while stack:
            e = stack.pop()
            if e in t.values:
                out.append(e)
            stack.extend(c for _, _, c in t.children[e])
        return out

    def text(self, obj):
        """The text's characters in document order."""
        values = self.texts[obj].values
        return ''.join(values[e] for e in self.elements(obj))

    def plain(self):
        return self._render(ROOT_ID)

    def _render(self, obj):
        if obj in self.texts:
            return self.text(obj)
        return {k: self._render(x) if kind == 'link' else x
                for k, (kind, x) in self.maps[obj].items()}


def replay(changes):
    doc = RefTextDoc()
    for c in changes:
        doc.apply(c)
    return doc
