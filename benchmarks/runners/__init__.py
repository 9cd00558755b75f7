"""One module per runner kind; a traffic file names its kind under `runner`."""
