"""Device time a step under the fused step's ``optimizer`` scope: Adam's
update of every parameter."""


def read(slice_):
    scopes = slice_.get('scopes')
    if not scopes or not slice_.get('steps'):
        return None
    return 1e3 * scopes['by_part'].get('optimizer', 0.0) / slice_['steps']
