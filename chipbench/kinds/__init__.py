"""One runner per kind of traffic file: ``train`` and ``serve``."""
