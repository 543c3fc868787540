"""One module per traffic mode, found by the ``mode`` of a traffic file."""
